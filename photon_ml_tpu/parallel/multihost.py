"""Multi-host (pod / DCN) runtime initialization.

Rebuild of ``SparkContextConfiguration.scala`` (YARN client setup — the
reference's "connect this process to the cluster" step) for the TPU
runtime: one ``jax.distributed.initialize`` call per host process, after
which ``jax.devices()`` spans every chip in the slice and the SAME mesh /
pjit code paths used single-host (``parallel.mesh``) scale across hosts —
in-slice collectives ride ICI, cross-slice ride DCN, both inserted by XLA
exactly like the single-host psums. There is no NCCL/MPI analog to manage:
the comm backend is the compiler's.

Joining is triggered ONLY by explicit configuration — the
JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID environment
variables or the matching arguments. (Cloud TPU metadata can fill the
process topology once initialize() runs, but metadata presence alone is
not treated as a signal: dev images and single-chip machines carry
pod-ish variables, and a misfired join hangs waiting for peers.)

Typical driver usage::

    from photon_ml_tpu.parallel import initialize_multihost, make_mesh

    initialize_multihost()           # no-op when single-process
    mesh = make_mesh()               # now spans the whole slice
    models = distributed_train_glm(batch, config, mesh)

Per-host data loading: each process should ingest ONLY its shard of rows
(e.g. its subset of Avro part files) and place them with
``jax.make_array_from_process_local_data`` onto a global mesh — the
multi-host generalization of ``shard_batch``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Callable, Optional

import jax

from photon_ml_tpu.resilience import faults as _faults

_INITIALIZED = False


# ---------------------------------------------------------------------------
# collective watchdog (docs/MULTIHOST.md)
# ---------------------------------------------------------------------------
#
# Every host-side collective in this module blocks until EVERY process
# arrives — which means one dead or wedged peer turns the whole pod into
# a silent hang. The watchdog bounds that: a configured deadline runs the
# exchange on a worker thread, abandons an attempt that outlives it
# (same abandon-the-thread shape as the ingest-pipeline stage watchdog —
# a hung gRPC exchange cannot be cancelled, only orphaned), records the
# stall (``collective.stalls`` counter, ``collective.stall_ms``
# histogram, a ``collective.stall`` event with straggler attribution
# from the heartbeat monitor when one is installed), and retries through
# the resilience backoff seam. On a REAL POD the retry is gated on the
# abandoned attempt having terminated: an orphan still in flight could
# be matched by peers against a reissued exchange, desyncing collective
# issue-order across processes — so a live orphan escalates as
# CollectiveAbandoned (fatal, straight to the host-loss contract)
# instead of retrying, and an orphan that completed late has its result
# consumed rather than reissued. A stall that survives the retry budget
# surfaces as RetryBudgetExceeded whose cause is CollectiveTimeout —
# which the drivers map to the host-loss exit contract
# (resilience.hostloss) instead of hanging until the scheduler's
# preemption timer fires.


class CollectiveTimeout(OSError):
    """A host collective exceeded its watchdog deadline. Subclasses
    OSError so the retry seam classifies it as transient — a straggler
    host may still arrive on the retry; a DEAD host exhausts the budget
    and escalates to the host-loss contract."""

    def __init__(self, label: str, timeout_s: float, attempt: int):
        super().__init__(
            f"collective {label!r} exceeded its {timeout_s:.3g}s watchdog "
            f"deadline (attempt {attempt})"
        )
        self.label = label
        self.timeout_s = timeout_s
        self.attempt = attempt


class CollectiveAbandoned(RuntimeError):
    """A watchdog-abandoned collective attempt was STILL in flight when
    the retry came due on a real pod. Reissuing the exchange while the
    orphaned attempt may yet match a peer's collective would desync
    issue-order across processes (peers could pair the orphan with this
    process's new exchange — mismatched data or a permanent wedge), so
    instead of retrying this escalates straight to the host-loss
    contract (``resilience.is_host_loss`` recognizes it). Deliberately
    NOT an ``OSError``: the retry seam must not classify it as
    transient."""

    def __init__(self, label: str, waited_s: float):
        super().__init__(
            f"collective {label!r} abandoned: a timed-out attempt was "
            f"still in flight {waited_s:.3g}s after issue — reissuing "
            "would desync collective order across processes; escalating "
            "to the host-loss contract"
        )
        self.label = label
        self.waited_s = waited_s


@dataclasses.dataclass
class CollectiveResilience:
    """Watchdog policy for host-side collectives. ``timeout_s`` None
    (default) keeps the bare blocking exchange — zero thread overhead,
    the pre-existing behavior."""

    timeout_s: Optional[float] = None
    retries: int = 2


_RESILIENCE = CollectiveResilience()


def configure_collective_resilience(
    timeout_s: Optional[float] = None, retries: int = 2
) -> CollectiveResilience:
    """Install the watchdog policy for every host collective in this
    module (the ``--collective-timeout-s`` surface). Returns the
    PREVIOUS policy so drivers can restore it."""
    global _RESILIENCE
    if timeout_s is not None and timeout_s <= 0:
        raise ValueError(f"timeout_s must be > 0, got {timeout_s}")
    if retries < 0:
        raise ValueError(f"retries must be >= 0, got {retries}")
    prev = _RESILIENCE
    _RESILIENCE = CollectiveResilience(timeout_s=timeout_s, retries=retries)
    return prev


def collective_resilience() -> CollectiveResilience:
    return _RESILIENCE


def _note_stall(label: str, waited_s: float, attempt: int) -> None:
    """Record one watchdog trip: metrics + a straggler-attributed event
    (riding the flight recorder when installed) BEFORE the pod would
    otherwise deadlock in silence."""
    from photon_ml_tpu import obs

    reg = obs.registry()
    reg.inc("collective.stalls")
    reg.observe("collective.stall_ms", waited_s * 1e3)
    slowest_host, slowest_age = None, None
    try:
        from photon_ml_tpu.parallel.heartbeat import current_monitor

        mon = current_monitor()
        if mon is not None and mon.slowest() is not None:
            slowest_host, slowest_age = mon.slowest()
            reg.set_gauge("pod.heartbeat.slowest_host", slowest_host)
            reg.set_gauge(
                "pod.heartbeat.slowest_age_s", round(slowest_age, 4)
            )
    except Exception:  # noqa: BLE001 — attribution is best-effort
        pass
    obs.emit_event(
        "collective.stall",
        cat="collective",
        label=label,
        waited_s=round(waited_s, 4),
        attempt=attempt,
        slowest_host=slowest_host,
        slowest_age_s=(
            round(slowest_age, 4) if slowest_age is not None else None
        ),
    )


def _resilient_exchange(label: str, fn: Callable):
    """Run one host collective under the configured watchdog + retry
    policy. Probes fault site ``collective.stall`` (key = label) inside
    each attempt, so a delay-mode drill stalls the attempt exactly like
    a straggler host and a raise-mode ``collective.allreduce`` spec (the
    PR-10 seam, probed by the call sites themselves) exercises the same
    retry path a dying peer does."""
    cfg = _RESILIENCE

    def attempt_body():
        _faults.fire("collective.stall", key=label)
        return fn()

    if cfg.timeout_s is None:
        return attempt_body()

    from photon_ml_tpu.resilience import retry as _retry

    attempts = [0]
    # the last abandoned attempt: (thread, result cell, error cell,
    # issue time). Multi-process, a retry must not reissue the exchange
    # while this may still be in flight — peers could match the orphan
    # against the new issue and every host's collective stream desyncs.
    orphan: list = [None]

    def deadline_attempt():
        attempts[0] += 1
        prev = orphan[0]
        if prev is not None:
            orphan[0] = None
            p_thread, p_result, p_error, p_t0 = prev
            if jax.process_count() > 1:
                # gate the reissue on the orphan terminating: give the
                # straggler one more deadline to arrive
                p_thread.join(cfg.timeout_s)
                if p_thread.is_alive():
                    waited = time.perf_counter() - p_t0
                    from photon_ml_tpu import obs

                    obs.registry().inc("collective.abandoned")
                    obs.emit_event(
                        "collective.abandoned",
                        cat="collective",
                        label=label,
                        waited_s=round(waited, 4),
                        attempt=attempts[0],
                    )
                    raise CollectiveAbandoned(label, waited)
                if p_result:
                    # the straggler arrived after all: the exchange
                    # COMPLETED with this process's contribution, so
                    # consuming its result (instead of issuing a fresh
                    # exchange) keeps every host's stream aligned
                    return p_result[0]
                # orphan failed cleanly — nothing of this attempt is in
                # flight any more; a fresh issue is safe (fall through)
            # single-process emulation: there is no cross-process stream
            # to desync — drills keep the abandon-and-retry shape

        result: list = []
        error: list = []

        def work():
            try:
                result.append(attempt_body())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                error.append(e)

        t = threading.Thread(
            target=work, name=f"collective-{label}", daemon=True
        )
        t0 = time.perf_counter()
        t.start()
        t.join(cfg.timeout_s)
        if t.is_alive():
            # the attempt is ABANDONED (a hung exchange has no cancel);
            # whether its eventual result may be used is decided at the
            # top of the NEXT attempt (pod: only if it terminated)
            _note_stall(label, time.perf_counter() - t0, attempts[0])
            orphan[0] = (t, result, error, t0)
            raise CollectiveTimeout(label, cfg.timeout_s, attempts[0])
        if error:
            raise error[0]
        return result[0]

    return _retry.retry_call(
        deadline_attempt,
        retries=cfg.retries,
        label=f"collective {label}",
    )


def resilient_host_exchange(label: str, fn: Callable):
    """Public seam for CUSTOM host-side exchange points — per-shard sync
    barriers, straggler-sensitive assembly steps — wanting the same
    watchdog + retry + stall-attribution policy the built-in collectives
    ride (:func:`configure_collective_resilience`). ``fn`` must block
    until the exchange completes; the ``shard_skew`` chaos drill drives
    a deliberately slow shard through this seam
    (docs/PARALLEL.md, docs/ROBUSTNESS.md)."""
    return _resilient_exchange(label, fn)


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join this process to the multi-host runtime. Returns True when a
    multi-process runtime was initialized, False for the single-process
    no-op (so drivers can call it unconditionally).

    Arguments default to the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
    JAX_PROCESS_ID environment variables, and on Cloud TPU to the
    platform's auto-detection. Safe to call twice (second call no-ops)."""
    global _INITIALIZED
    if _INITIALIZED:
        return True

    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])

    # Join only on an EXPLICIT signal (argument or env var). TPU-metadata
    # auto-detection is deliberately not used as the trigger: single-chip
    # machines and dev images carry pod-ish variables, and a misfired
    # initialize() hangs waiting for peers.
    if not (coordinator_address or (num_processes or 0) > 1):
        return False  # single-process run: nothing to join

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _INITIALIZED = True
    # pod observability identity (obs.dist): every tracer/metrics
    # artifact from here on is stamped host.<i>, and — when a tracer is
    # already installed — a barrier-backed clock.sync event anchors this
    # process's trace shard so `photon-obs merge` can lay all hosts on
    # one timeline regardless of per-host clock skew
    emit_pod_sync()
    return True


def emit_pod_sync() -> None:
    """Stamp this process's obs identity from the live jax runtime and
    emit a barrier-backed ``clock.sync`` trace event (no-op untraced;
    the identity stamp always happens). Called by
    :func:`initialize_multihost`; callable again by drivers that install
    their tracer after joining."""
    from photon_ml_tpu.obs import dist as obs_dist

    obs_dist.set_process_identity(jax.process_index(), jax.process_count())
    barrier = None
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        def barrier():
            # barriers are collectives too: a dead peer would wedge the
            # sync forever, so it rides the same watchdog/retry seam
            _resilient_exchange(
                "pod_sync",
                lambda: multihost_utils.sync_global_devices(
                    "photon-obs-clock-sync"
                ),
            )

    obs_dist.emit_clock_sync(sync_id="startup", barrier=barrier)


def hierarchical_psum(x, intra_axis: str = "device", inter_axis: str = "host"):
    """Two-level all-reduce for use INSIDE ``shard_map`` over a
    ('host', 'device') mesh (``parallel.mesh.make_host_device_mesh``):

        1. reduce-scatter over the fast intra-host (ICI) axis — each
           device ends holding 1/D of the fully-intra-reduced payload;
        2. all-reduce the already-reduced 1/D shards over the slow
           inter-host (DCN) axis — the ONLY cross-host traffic, payload
           1/D of what a flat all-reduce would put on DCN;
        3. all-gather over the intra axis to re-replicate.

    The flat ``lax.psum(x, (intra, inter))`` moves the FULL payload over
    whichever links the compiler picks; this pins the reduction order so
    DCN — the link an order of magnitude thinner than ICI on a multi-pod
    slice — only ever carries the 1/D partials (the TPU analog of the
    reference bumping ``treeAggregate`` depth above 200k features,
    ``cli/game/training/Driver.scala:336-341``). Works on any pytree;
    leaves flatten, pad to a multiple of the intra-axis size, and
    reassemble, so payload shapes need no alignment. Numerics: identical
    operand multisets per element, different association than the flat
    psum — agreement to f32 rounding, drilled <= 1e-6/1e-12 in
    tests/test_partition.py. Single-process emulation: a
    ``make_host_device_mesh`` over virtual CPU devices exercises the
    exact same program."""
    import jax.numpy as jnp
    from jax import lax

    n_intra = lax.psum(1, intra_axis)

    def reduce_leaf(leaf):
        leaf = jnp.asarray(leaf)
        flat = leaf.reshape(-1)
        size = flat.shape[0]
        pad = (-size) % n_intra
        if pad:
            flat = jnp.pad(flat, (0, pad))
        scat = lax.psum_scatter(flat, intra_axis, tiled=True)
        part = lax.psum(scat, inter_axis)
        full = lax.all_gather(part, intra_axis, tiled=True)
        if pad:
            full = full[:size]
        return full.reshape(leaf.shape)

    return jax.tree_util.tree_map(reduce_leaf, x)


def split_rows(total_rows: int, num_processes: int, process_id: int) -> range:
    """Contiguous even split of a global row space: the ranges over all
    process ids are disjoint and cover [0, total_rows)."""
    per = -(-total_rows // num_processes)
    return range(
        min(process_id * per, total_rows),
        min((process_id + 1) * per, total_rows),
    )


def _require_joined(caller: str) -> None:
    """A configured-but-unjoined runtime is a hard error: input-split
    helpers called before :func:`initialize_multihost` would silently
    hand every host the full input (duplicated ingest, corrupt global
    arrays). "Configured" means ANY of the join triggers is set — the
    same signals initialize_multihost() joins on."""
    if _INITIALIZED or jax.process_count() > 1:
        # joined (possibly a single-process pod smoke test): splits are
        # whatever process_count says
        return
    configured = int(os.environ.get("JAX_NUM_PROCESSES", "1") or "1")
    coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if configured > 1 or coordinator:
        raise RuntimeError(
            f"multi-host runtime configured (JAX_NUM_PROCESSES="
            f"{configured}, JAX_COORDINATOR_ADDRESS={coordinator!r}) but "
            f"this process has not joined; call initialize_multihost() "
            f"before {caller}()"
        )


def process_local_paths(paths):
    """The subset of input part files THIS process should ingest — file
    granularity input splits (round-robin by sorted position, so hosts
    get near-equal counts even when the file list grows). Feed the result
    to ``io.ingest.IngestSource``; each host then decodes only its slice
    in parallel threads and places rows globally with
    ``jax.make_array_from_process_local_data``. Single-process: all
    paths. Same join-first contract as :func:`process_local_rows`."""
    _require_joined("process_local_paths")
    paths = sorted(paths)
    n = jax.process_count()
    # symmetric failure: EVERY host raises when any host's slice would be
    # empty — one host erroring while the rest proceed to collectives
    # turns a config error into a distributed hang
    if len(paths) < n:
        raise ValueError(
            f"{len(paths)} part files for {n} processes — every process "
            "needs at least one input file"
        )
    return paths[jax.process_index()::n]


def make_global_array(x, mesh):
    """One process-local array -> one GLOBAL jax.Array: every process
    contributes its rows, concatenated in process order along axis 0 and
    sharded over all mesh axes flattened (``mesh.batch_sharding``). All
    processes must contribute the SAME local shape."""
    import numpy as np

    from photon_ml_tpu.parallel.mesh import batch_sharding

    x = np.asarray(x)
    sharding = batch_sharding(mesh, x.ndim)
    global_shape = (x.shape[0] * jax.process_count(),) + x.shape[1:]
    return jax.make_array_from_process_local_data(sharding, x, global_shape)


def allgather_host(x):
    """Small HOST array -> the concatenation of every process's value
    (process order, axis 0), returned as a host numpy array on every
    process. The bookkeeping primitive for globalizing per-process
    metadata (entity counts, lane->table index vectors).

    Host-blocking by construction, so the collective profiler
    (``obs.collectives``) gets a TRUE per-exchange wall: every call
    records ``collective.allgather_host.w<nproc>.{count,bytes,wall_ms}``
    and, when traced, a ``collective.allgather_host`` span.

    With a watchdog configured (:func:`configure_collective_resilience`
    / ``--collective-timeout-s``), the exchange runs under a deadline
    and retries through the resilience backoff seam instead of wedging
    the pod on a dead peer; exhaustion surfaces the host-loss contract
    (docs/MULTIHOST.md)."""
    import numpy as np

    def exchange():
        # chaos seam: the multihost collective boundary. Probed INSIDE
        # the watchdogged attempt and BEFORE the single-process
        # early-return so drills exercise the seam without a pod:
        # raise-mode simulates a peer dying mid-exchange (the error a
        # real pod sees when a host drops), delay-mode a straggler host
        # that the watchdog times out.
        _faults.fire("collective.allreduce", key="allgather_host")
        if jax.process_count() == 1:
            return np.asarray(x)
        from jax.experimental import multihost_utils

        from photon_ml_tpu.obs import collectives as obs_coll

        arr = np.asarray(x)
        with obs_coll.collective_span(
            "allgather_host",
            mesh_width=jax.process_count(),
            nbytes=int(arr.nbytes),
        ):
            return np.asarray(
                multihost_utils.process_allgather(arr, tiled=True)
            )

    return _resilient_exchange("allgather_host", exchange)


def allgather_strings(strs):
    """Every process's list of strings -> one list concatenated in
    process order, identical on every process. Strings are utf-8 encoded
    into fixed-width uint8 rows (padded to the allgathered max length
    and count) so the exchange rides the same array allgather as
    everything else. The globalization primitive for ENTITY VOCABULARIES
    in multi-process GAME: each process indexes its own entities; the
    global raw-id -> table-row map is this concatenation."""
    import numpy as np

    if jax.process_count() == 1:
        return list(strs)
    enc = [s.encode("utf-8") for s in strs]
    local_count = len(enc)
    local_max = max((len(b) for b in enc), default=0)
    meta = allgather_host(
        np.asarray([[local_count, local_max]], np.int64)
    )  # (nproc, 2)
    max_count = int(meta[:, 0].max())
    max_len = max(int(meta[:, 1].max()), 1)
    buf = np.zeros((max_count, max_len), np.uint8)
    lens = np.zeros((max_count,), np.int64)
    for i, b in enumerate(enc):
        buf[i, : len(b)] = np.frombuffer(b, np.uint8)
        lens[i] = len(b)
    g_buf = allgather_host(buf).reshape(-1, max_count, max_len)
    g_lens = allgather_host(lens).reshape(-1, max_count)
    out = []
    for p in range(jax.process_count()):
        for i in range(int(meta[p, 0])):
            out.append(
                g_buf[p, i, : g_lens[p, i]].tobytes().decode("utf-8")
            )
    return out


def global_entity_space(local_num_entities: int):
    """(num_entities_global, entity_base) for THIS process: entities are
    process-partitioned (the TPU analog of the reference's
    ``RandomEffectIdPartitioner`` placement — every entity's rows live in
    exactly one process's input split), and the global coefficient-table
    row for this process's local entity e is ``entity_base + e``."""
    import numpy as np

    counts = allgather_host(np.asarray([local_num_entities], np.int64))
    base = int(counts[: jax.process_index()].sum())
    return int(counts.sum()), base


# one jitted identity-reshard per mesh: a fresh jit per call would
# retrace/re-lower on every fetched leaf of every update (the pjit cache
# keys on function identity)
_REPLICATE_JIT_CACHE: dict = {}


def reshard_replicated(x):
    """Non-fully-addressable global jax.Array -> the same value resharded
    REPLICATED (one all-gather, still on device, now fully addressable —
    so a later batched ``jax.device_get`` can fetch it with everything
    else in one transfer). Addressable arrays and non-arrays pass
    through unchanged."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        from jax.sharding import NamedSharding, PartitionSpec

        mesh = x.sharding.mesh
        fn = _REPLICATE_JIT_CACHE.get(mesh)
        if fn is None:
            fn = jax.jit(
                lambda a: a,
                out_shardings=NamedSharding(mesh, PartitionSpec()),
            )
            _REPLICATE_JIT_CACHE[mesh] = fn
        return fn(x)
    return x


def fetch_replicated(x):
    """Materialize ANY value on host as numpy — jax.Arrays (including
    global arrays with non-addressable shards, which reshard to
    replicated first) transfer synchronously; non-arrays pass through.
    For BATCHED drains prefer ``reshard_replicated`` + one
    ``jax.device_get`` over per-leaf calls here (each np.asarray is a
    synchronous transfer)."""
    import numpy as np

    out = reshard_replicated(x)
    if isinstance(out, jax.Array):
        return np.asarray(out)
    return out


def make_global_re_design(
    design,
    mesh,
    num_entities_global: int,
    entity_base: int,
    row_base: int,
):
    """Local (per-process) random-effect design -> GLOBAL design whose
    bucket lanes concatenate over processes and shard over the mesh.

    Contract (the reference's ``RandomEffectIdPartitioner`` placement,
    ``data/RandomEffectDataSet.scala:39-381``): input rows are
    ENTITY-PARTITIONED across processes — every entity's rows live in
    exactly one process's split — and all processes build with the SAME
    num_buckets and bucket shapes (pin ``active_cap``; shapes must match
    across processes or the global assembly is rejected by the runtime).

    ``entity_base``/``num_entities_global`` come from
    :func:`global_entity_space`; ``row_base`` is this process's offset in
    the global row space (n_local * process_index for even splits) so
    per-pass residual gathers hit the right global rows. Bucket lane ->
    table row indices are allgathered host-side (small int vectors);
    local pad sentinels remap to the global sentinel.

    Processes may hold DIFFERENT entity counts / row caps per bucket:
    every process's bucket is padded to the allgathered max lane count
    (rounded up to the local device count so the global lane axis shards
    evenly) and max row cap before assembly; pad lanes carry the global
    sentinel and zero masks, so gathers clip and scatters drop them."""
    import numpy as np

    from photon_ml_tpu.game.data import (
        BucketedRandomEffectDesign,
        RandomEffectDesign,
    )

    if isinstance(design, RandomEffectDesign):
        design = BucketedRandomEffectDesign(
            buckets=[design],
            entity_index=[
                np.arange(design.num_entities, dtype=np.int32)
            ],
            num_entities=design.num_entities,
        )
    n_buckets = allgather_host(np.asarray([design.num_buckets], np.int64))
    if not (n_buckets == n_buckets[0]).all():
        raise ValueError(
            f"processes built different bucket counts {n_buckets.tolist()}"
            " — pin num_buckets in the coordinate spec"
        )
    g_buckets, g_index = [], []
    local_dev = jax.local_device_count()
    for bucket, eidx in zip(design.buckets, design.entity_index):
        shapes = allgather_host(
            np.asarray(
                [[bucket.num_entities, bucket.rows_per_entity]], np.int64
            )
        )  # (nproc, 2)
        e_max = int(shapes[:, 0].max())
        e_max = -(-e_max // local_dev) * local_dev
        r_max = int(shapes[:, 1].max())
        feats = np.asarray(bucket.features)
        e_loc, r_loc, dim = feats.shape
        pe, pr = e_max - e_loc, r_max - r_loc

        def pad2(x, fill=0.0):
            return np.pad(
                np.asarray(x), ((0, pe), (0, pr)), constant_values=fill
            )

        ri = np.asarray(bucket.row_index)
        ri = np.where(ri >= 0, ri + row_base, -1).astype(np.int32)
        g_buckets.append(
            RandomEffectDesign(
                features=make_global_array(
                    np.pad(feats, ((0, pe), (0, pr), (0, 0))), mesh
                ),
                labels=make_global_array(pad2(bucket.labels), mesh),
                weights=make_global_array(pad2(bucket.weights), mesh),
                mask=make_global_array(pad2(bucket.mask), mesh),
                row_index=make_global_array(pad2(ri, fill=-1), mesh),
            )
        )
        ei = np.asarray(eidx)
        ei_g = np.where(
            ei < design.num_entities,
            ei + entity_base,
            num_entities_global,
        ).astype(np.int32)
        ei_g = np.pad(
            ei_g, (0, e_max - ei_g.shape[0]),
            constant_values=num_entities_global,
        )
        g_index.append(allgather_host(ei_g))
    return BucketedRandomEffectDesign(
        buckets=g_buckets,
        entity_index=g_index,
        num_entities=num_entities_global,
    )


def make_global_batch(local_batch, mesh):
    """Assemble a GLOBAL row-sharded batch from THIS process's local rows
    (the multi-host generalization of ``mesh.shard_batch``): every leaf
    becomes a ``jax.Array`` spanning the whole mesh via
    ``jax.make_array_from_process_local_data``, with this process's rows
    living on its addressable devices. All processes must hold the SAME
    number of rows (use file- or row-splits that divide evenly; pad the
    local batch first otherwise) and, for structured features, the same
    static widths — pin the padded-ELL width with
    ``labeled_batch(..., nnz_per_row=...)`` so every host's local decode
    produces identical shapes. Single-process: equivalent to
    ``shard_batch`` without the padding."""
    import jax.tree_util as jtu

    return jtu.tree_map(lambda x: make_global_array(x, mesh), local_batch)


def process_local_rows(total_rows: int) -> range:
    """The contiguous row range THIS process should ingest — the even
    split of a global row space over processes (the analog of the
    reference's input-split assignment). Single-process: everything.

    Must run AFTER :func:`initialize_multihost` on a pod (see
    :func:`_require_joined`)."""
    _require_joined("process_local_rows")
    return split_rows(total_rows, jax.process_count(), jax.process_index())
