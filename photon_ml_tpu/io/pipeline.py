"""Streaming ingest->device pipeline: parallel decode, double-buffered
prefetch, out-of-core epochs.

The reference feeds training from a fleet of JVM executors
(``avro/AvroIOUtils.scala:46-139``); a single TPU host must instead keep
the device fed from one process. BENCH_r05 measured native ingest at
116k rec/s and 14.4 s to move 0.512 GB host->device — after PR 8 made
the solve single-dispatch, the feed IS the wall. This module is the
train-side data path rebuilt as a pipeline whose stages overlap:

1. **Parallel decode** — input files are planned into ``chunk_mb``-sized
   file groups and decoded on a bounded thread pool (one
   :class:`~photon_ml_tpu.io.native.NativeAvroReader` per file per
   attempt, context-managed so retries never leak native handles; the
   ctypes decode releases the GIL, so groups genuinely overlap).
   Emission is ORDER-PRESERVING and bounded: decode never runs more
   than ``prefetch_depth`` groups ahead of consumption, and a transient
   read failure retries through the ``ingest.read`` fault/retry seam
   without duplicating or dropping a chunk.
2. **Staging** — decoded columns are cut into uniform ``rows_per_chunk``
   row blocks and written into a PREALLOCATED ring of host staging
   buffers (``prefetch_depth + 1`` slots; a slot is reused only after
   the device transfer issued from it completed), so steady-state
   staging allocates nothing and every chunk has ONE compiled shape.
3. **Transfer** — each staged chunk is handed to an async
   ``jax.device_put`` so chunk N+1's decode and transfer overlap chunk
   N's consumption; device-side assembly reuses the PR-4 destructive
   deposit (donated ``dynamic_update_slice``) under an
   ``hbm_watermark`` so the dataset-plus-one-chunk peak stays
   observable.
4. **Out-of-core epochs** — :class:`StreamedDesign` keeps the chunks
   host-side and :class:`StreamingObjective` streams them through the
   fused objective passes per solver iteration, accumulating
   value/grad/curvature partials in a donated-carry accumulate program;
   TRON/L-BFGS see the exact full-dataset objective
   (``models.training.train_glm_streamed``), equivalence-drilled to
   1e-10 against the in-core solve.

Every stage is instrumented through :mod:`photon_ml_tpu.obs`:
``ingest.decode`` / ``ingest.stage`` / ``ingest.transfer`` spans,
``ingest.pipeline.*`` metrics, and pipeline-stall counters, so the
overlap is visible in Perfetto and gated by the bench sentinel
(docs/INGEST.md).
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.obs import quality as _quality
from photon_ml_tpu.resilience import faults as _faults

DEFAULT_CHUNK_MB = 64.0
DEFAULT_PREFETCH_DEPTH = 2

EPOCH_POLICIES = ("fail", "skip")


class StageStall(OSError):
    """A pipeline stage blew past its watchdog deadline. Subclasses
    OSError so the existing retry seam treats a stall exactly like a
    transient read failure: the abandoned attempt is cancelled (its
    worker thread is orphaned — daemon, never joined) and the stage
    re-runs cleanly."""

    def __init__(self, stage: str, label: str, timeout_s: float):
        super().__init__(
            f"pipeline stage {stage!r} stalled past {timeout_s}s "
            f"({label})"
        )
        self.stage = stage
        self.timeout_s = timeout_s


def _with_watchdog(
    fn,
    timeout_s: Optional[float],
    stage: str,
    label: str,
    on_abandon=None,
):
    """Run ``fn()`` under a stall deadline: the work moves to a daemon
    thread and the caller waits at most ``timeout_s``. On stall the
    attempt is abandoned and :class:`StageStall` raises into the retry
    seam (cancel-and-redo semantics — the cleanest cancellation python
    threads allow); ``on_abandon(thread)`` lets the owner track the
    stray so shared native state isn't freed under it. ``timeout_s``
    None/0 runs ``fn`` inline: unwatched stages pay nothing."""
    if not timeout_s:
        return fn()
    box: Dict[str, object] = {}
    done = threading.Event()

    def run():
        try:
            box["ok"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            box["err"] = e
        finally:
            done.set()

    t = threading.Thread(
        target=run, name=f"watchdog-{stage}", daemon=True
    )
    t.start()
    if not done.wait(timeout_s):
        if on_abandon is not None:
            on_abandon(t)
        reg = obs.registry()
        reg.inc("ingest.pipeline.watchdog_stalls")
        reg.inc(f"ingest.pipeline.watchdog_stalls.{stage}")
        obs.emit_event(
            "io.pipeline.stall",
            cat="io",
            stage=stage,
            label=label,
            timeout_s=timeout_s,
        )
        raise StageStall(stage, label, timeout_s)
    if "err" in box:
        raise box["err"]  # type: ignore[misc]
    return box.get("ok")


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """The ingest-pipeline knobs (``--ingest-chunk-mb`` /
    ``--decode-threads`` / ``--prefetch-depth`` on the train drivers).

    chunk_mb: target decoded-chunk size. Plans input files into decode
    groups by cumulative on-disk size AND sizes the uniform staged row
    blocks (``rows_per_chunk = chunk_mb / row_bytes``).
    decode_threads: concurrent decode workers; 0 = auto (core count,
    honoring the ``PHOTON_DECODE_THREADS`` override — capped and logged
    once by :func:`photon_ml_tpu.io.native._default_decode_threads`).
    prefetch_depth: how many chunks decode/staging may run ahead of the
    consumer; also sizes the staging ring (depth + 1 slots). 1 is the
    classic double buffer's minimum; 2 (default) absorbs decode jitter.
    stage_timeout_s: per-stage watchdog deadline (decode / stage /
    transfer). A stage that stalls past it is cancelled and re-run
    through the retry seam; None (default) disables the watchdogs.
    epoch_policy: what an EXHAUSTED retry budget does to the epoch —
    ``"fail"`` (default) raises, ``"skip"`` logs the lost group, counts
    it (``ingest.pipeline.groups_skipped``), and continues the epoch
    without those rows (availability over completeness; the consumer
    sees fewer rows, never wrong ones).
    """

    chunk_mb: float = DEFAULT_CHUNK_MB
    decode_threads: int = 0
    prefetch_depth: int = DEFAULT_PREFETCH_DEPTH
    stage_timeout_s: Optional[float] = None
    epoch_policy: str = "fail"

    def validate(self) -> None:
        if not self.chunk_mb > 0:
            raise ValueError(f"chunk_mb must be > 0, got {self.chunk_mb}")
        if self.decode_threads < 0:
            raise ValueError(
                f"decode_threads must be >= 0 (0 = auto), got "
                f"{self.decode_threads}"
            )
        if self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}"
            )
        if self.stage_timeout_s is not None and not self.stage_timeout_s > 0:
            raise ValueError(
                f"stage_timeout_s must be > 0 or None, got "
                f"{self.stage_timeout_s}"
            )
        if self.epoch_policy not in EPOCH_POLICIES:
            raise ValueError(
                f"epoch_policy must be one of {EPOCH_POLICIES}, got "
                f"{self.epoch_policy!r}"
            )


def plan_file_groups(
    files: Sequence[str], chunk_mb: float
) -> List[List[str]]:
    """Input files -> decode groups by cumulative on-disk size. Each
    group is one decode-pool work unit (whole files only — container
    blocks inside one file already parallelize natively); a file larger
    than the budget becomes its own group."""
    budget = chunk_mb * (1 << 20)
    groups: List[List[str]] = []
    cur: List[str] = []
    size = 0.0
    for f in files:
        try:
            s = float(os.path.getsize(f))
        except OSError:
            s = budget  # unknown size: conservatively its own group
        if cur and size + s > budget:
            groups.append(cur)
            cur, size = [], 0.0
        cur.append(f)
        size += s
    if cur:
        groups.append(cur)
    return groups


class PipelineStats:
    """Thread-safe per-stage busy-time accumulators for one pipeline
    run. ``overlap_frac`` is the counted-stage overlap — the fraction
    of total stage busy time hidden by pipelining (0 when the stages
    ran strictly serially; > 0 whenever two stages were in flight at
    once) — and ``stall_frac`` the fraction of the wall the consumer
    spent waiting on decode. Both feed the bench sentinel
    (``transfer_overlap_frac`` higher-better, ``epoch_stall_frac``
    lower-better)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.decode_s = 0.0
        self.stage_s = 0.0
        self.transfer_s = 0.0
        self.consume_s = 0.0
        self.stall_s = 0.0
        self.wall_s = 0.0
        self.chunks = 0
        self.records = 0
        self.bytes_to_device = 0
        self.stalls = 0
        self.retries = 0
        self.groups_skipped = 0
        # counted stage intervals (stage, start, end) in perf_counter
        # time — the overlap evidence. Bounded: a pipeline emits a few
        # intervals per chunk.
        self._intervals: List[Tuple[str, float, float]] = []

    def note(
        self,
        stage: str,
        seconds: float,
        t0: Optional[float] = None,
        **inc,
    ) -> None:
        with self._lock:
            setattr(self, f"{stage}_s", getattr(self, f"{stage}_s") + seconds)
            if t0 is not None and seconds > 0:
                self._intervals.append((stage, t0, t0 + seconds))
            for k, v in inc.items():
                setattr(self, k, getattr(self, k) + v)

    def note_stall(self, seconds: float) -> None:
        with self._lock:
            self.stall_s += seconds
            self.stalls += 1

    def finish(self, wall_s: float) -> "PipelineStats":
        with self._lock:
            self.wall_s += wall_s
        return self

    def busy_s(self) -> float:
        return self.decode_s + self.stage_s + self.transfer_s + self.consume_s

    def overlap_frac(self) -> float:
        """Fraction of stage-covered wall time during which TWO OR MORE
        counted stage intervals were in flight (sweep line over the
        recorded spans). 0 = strictly serial stages; > 0 = the pipeline
        actually pipelined (decode ahead of staging, transfer under
        consume, parallel decode workers)."""
        with self._lock:
            ivs = list(self._intervals)
        if not ivs:
            return 0.0
        events: List[Tuple[float, int]] = []
        for _, a, b in ivs:
            events.append((a, 1))
            events.append((b, -1))
        events.sort()
        union = 0.0
        multi = 0.0
        depth = 0
        prev = events[0][0]
        for t, d in events:
            if t > prev:
                if depth >= 1:
                    union += t - prev
                if depth >= 2:
                    multi += t - prev
            prev = t
            depth += d
        return multi / union if union > 0 else 0.0

    def stall_frac(self) -> float:
        if self.wall_s <= 0.0:
            return 0.0
        return min(1.0, self.stall_s / self.wall_s)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out = {
                "decode_s": self.decode_s,
                "stage_s": self.stage_s,
                "transfer_s": self.transfer_s,
                "consume_s": self.consume_s,
                "stall_s": self.stall_s,
                "wall_s": self.wall_s,
                "chunks": float(self.chunks),
                "records": float(self.records),
                "bytes_to_device": float(self.bytes_to_device),
                "stalls": float(self.stalls),
                "retries": float(self.retries),
                "groups_skipped": float(self.groups_skipped),
            }
        out["overlap_frac"] = self.overlap_frac()
        out["stall_frac"] = self.stall_frac()
        return out


class _StagingRing:
    """Preallocated host staging buffers, reused round-robin. A slot is
    handed out again only after the device transfer issued from it has
    completed (``block_until_ready`` on the array it fed — by then the
    transfer is ``prefetch_depth`` chunks old, so the wait is ~free),
    which makes reuse safe even on runtimes where ``device_put`` reads
    the host buffer asynchronously."""

    def __init__(self, nslots: int):
        self._slots: List[Optional[Dict[str, np.ndarray]]] = [None] * nslots
        self._inflight: List[object] = [None] * nslots
        self._next = 0

    def acquire(self, rows: int, d: int, dtype) -> Tuple[int, Dict[str, np.ndarray]]:
        s = self._next % len(self._slots)
        self._next += 1
        dev = self._inflight[s]
        if dev is not None:
            try:
                for leaf in dev:
                    leaf.block_until_ready()
            except Exception:
                pass
            self._inflight[s] = None
        buf = self._slots[s]
        if (
            buf is None
            or buf["features"].shape != (rows, d)
            or buf["features"].dtype != np.dtype(dtype)
        ):
            buf = {
                "features": np.zeros((rows, d), dtype),
                "labels": np.zeros((rows,), dtype),
                "offsets": np.zeros((rows,), dtype),
                "weights": np.zeros((rows,), dtype),
                "mask": np.zeros((rows,), dtype),
            }
            self._slots[s] = buf
        return s, buf

    def note_transfer(self, slot: int, device_arrays) -> None:
        self._inflight[slot] = device_arrays


@functools.lru_cache(maxsize=2)
def _device_copy_fn():
    import jax

    # NOT donated and NOT an identity XLA can alias away: the output is
    # a fresh device buffer, so once it is ready the host source may be
    # overwritten
    return jax.jit(lambda x: x * 1)


def _owned_device_copy(host: np.ndarray):
    """host array -> device array that OWNS its storage. A bare
    ``device_put`` may zero-copy (alias) the host buffer on CPU-class
    backends, which would let ring-slot reuse corrupt chunks still in
    flight; routing through a jitted copy materializes an owned device
    buffer, and ``block_until_ready`` on it really does mean the host
    slot is free to reuse."""
    return _device_copy_fn()(host)


@dataclasses.dataclass
class StagedChunk:
    """One uniform row block staged for transfer. ``features`` etc. are
    VIEWS INTO A RING SLOT — valid until ``prefetch_depth`` further
    chunks have been staged; consumers either transfer (device_put
    copies) or copy host-side before moving on."""

    index: int
    start_row: int
    rows: int  # real rows (< features.shape[0] only for a padded tail)
    features: np.ndarray
    labels: np.ndarray
    offsets: np.ndarray
    weights: np.ndarray
    mask: np.ndarray
    ring_slot: int = -1


def rows_per_chunk_for(chunk_mb: float, d: int, itemsize: int = 8) -> int:
    """Uniform staged-chunk row count: ``chunk_mb`` of dense row bytes
    (features + the four scalar columns)."""
    row_bytes = itemsize * (d + 4)
    return max(1, int(chunk_mb * (1 << 20) / max(row_bytes, 1)))


def _dense_part(part: dict, vocab, vocab_index: int) -> np.ndarray:
    """One decoded part's COO triplets -> its dense (n, d) float64 block
    with the intercept column injected — the same math as the one-shot
    ``IngestSource.labeled_batch`` per part, so the assembled dataset is
    bit-for-bit identical."""
    from photon_ml_tpu.io.ingest import _inject_intercept

    n = part["n"]
    d = len(vocab)
    rows, cols, vals = part["coo"][vocab_index]
    rows, cols, vals = _inject_intercept(
        rows, cols, vals, n, vocab.intercept_index
    )
    x = np.zeros((n, d), np.float64)
    np.add.at(x, (rows.astype(np.int64), cols.astype(np.int64)), vals)
    return x


class IngestPipeline:
    """Avro input files -> ordered stream of decoded parts / staged
    chunks / device chunks, with decode, staging and transfer overlapped.

    One pipeline instance is one pass over the input; :meth:`parts`,
    :meth:`chunks` and the assembly entry points each start a fresh
    decode pool. The native vocabulary hash maps build ONCE and are
    shared read-only across every per-file reader (and thread); use the
    pipeline as a context manager (or call :meth:`close`) to release
    them deterministically.
    """

    def __init__(
        self,
        paths: Sequence[str],
        vocabs: Sequence,
        entity_keys: Sequence[str] = (),
        label_field: str = "label",
        allow_null_labels: bool = False,
        config: PipelineConfig = PipelineConfig(),
        stats: Optional[PipelineStats] = None,
    ):
        from photon_ml_tpu.io import native

        config.validate()
        if not paths:
            raise FileNotFoundError("no input files")
        if native.get_lib() is None:
            raise RuntimeError(
                f"ingest pipeline requires the native reader: "
                f"{native.native_error()}"
            )
        self.files = list(paths)
        self.vocabs = list(vocabs)
        self.entity_keys = tuple(entity_keys)
        self.label_field = label_field
        self.allow_null_labels = allow_null_labels
        self.config = config
        self.stats = stats if stats is not None else PipelineStats()
        self._native = native
        self.groups = plan_file_groups(self.files, config.chunk_mb)
        cores = os.cpu_count() or 1
        env = native._env_decode_threads()
        auto = env if env is not None else min(len(self.groups), cores, 16)
        self.decode_workers = max(
            1, config.decode_threads or auto
        )
        # container blocks inside each file split the remaining cores
        self.block_threads = max(
            1, cores // max(1, min(self.decode_workers, len(self.groups)))
        )
        schema = native._read_header_schema(self.files[0])
        self._schema = schema
        self._field_prog, self._feat_desc = native.compile_schema(
            schema,
            label_field=label_field,
            want_entities=bool(self.entity_keys),
        )
        self._vocabset = native.NativeVocabSet(
            [v.index_to_key for v in self.vocabs],
            [v.intercept_index for v in self.vocabs],
        )
        self._closed = False
        # decode attempts abandoned by the stage watchdog: they still
        # hold the shared native vocab maps, so close() must not free
        # those under them (tracked only on stall — zero steady cost)
        self._stray_threads: List[threading.Thread] = []
        obs.emit_event(
            "io.pipeline.start",
            cat="io",
            files=len(self.files),
            groups=len(self.groups),
            decode_workers=self.decode_workers,
            block_threads=self.block_threads,
            chunk_mb=config.chunk_mb,
            prefetch_depth=config.prefetch_depth,
        )

    # -- lifecycle ----------------------------------------------------------

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            # wait out watchdog-abandoned decode attempts: they read the
            # shared native vocab maps, and freeing those under a live
            # native call is a use-after-free. A still-hung stray after
            # the grace period leaks the maps instead — a bounded leak
            # beats a segfault.
            for t in self._stray_threads:
                t.join(timeout=30.0)
            if any(t.is_alive() for t in self._stray_threads):
                obs.emit_event(
                    "io.pipeline.stray_leak",
                    cat="io",
                    threads=sum(
                        t.is_alive() for t in self._stray_threads
                    ),
                )
                return
            self._vocabset.close()

    def __enter__(self) -> "IngestPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- stage 1: parallel decode ------------------------------------------

    def _decode_group(self, index: int, group: List[str]) -> dict:
        """Decode one file group into a columnar part dict (the
        ``native.read_columnar`` schema). Each ATTEMPT builds fresh
        context-managed readers, so a mid-stream retry through the
        ``ingest.read`` fault seam restarts the group cleanly — no
        duplicated or dropped records."""
        from photon_ml_tpu.io.ingest import _resilient_read

        native = self._native

        def decode_once():
            # chaos seam: the decode-pool stage. raise-mode restarts the
            # group through the retry wrapper below (fresh readers —
            # no duplicated or dropped chunk); delay-mode is the stalled-
            # decoder drill the stage watchdog converts into a retry.
            _faults.fire("pipeline.decode", key=str(index))
            parts = []
            for path in group:
                with native.NativeAvroReader(
                    self._field_prog,
                    self._feat_desc,
                    self._vocabset,
                    self.entity_keys,
                ) as reader:
                    reader.feed_file(
                        path,
                        expected_schema=self._schema,
                        decode_threads=self.block_threads,
                    )
                    parts.append(
                        native._extract_columns(
                            reader, self.entity_keys, len(self.vocabs)
                        )
                    )
            return parts

        def decode_attempt():
            # watchdog: a stalled attempt (hung FS, wedged native call)
            # is abandoned after stage_timeout_s and re-decoded — the
            # StageStall is an OSError, so the retry seam owns the redo
            return _with_watchdog(
                decode_once,
                self.config.stage_timeout_s,
                "decode",
                f"chunk {index}",
                on_abandon=self._stray_threads.append,
            )

        t0 = time.perf_counter()
        with obs.span(
            "ingest.decode", cat="io", chunk=index, files=len(group)
        ):
            parts = _resilient_read(
                decode_attempt,
                label=f"pipeline decode chunk {index} ({group[0]}...)",
                paths=group,
            )
        part = parts[0] if len(parts) == 1 else _merge_parts(
            parts, self.entity_keys, len(self.vocabs)
        )
        if not self.allow_null_labels and not part["label_present"].all():
            i = int(np.argmin(part["label_present"]))
            raise ValueError(
                f"record {i} of chunk {index} ({group}) has a null/"
                "missing label; training input requires labels (pass "
                "allow_null_labels=True only for scoring)"
            )
        dt = time.perf_counter() - t0
        self.stats.note("decode", dt, t0=t0, records=part["n"])
        reg = obs.registry()
        reg.observe("ingest.pipeline.decode_ms", dt * 1e3)
        reg.inc("ingest.pipeline.records", part["n"])
        return part

    def _skip_group(self, index: int, err: BaseException) -> bool:
        """Epoch policy on an exhausted decode-retry budget: ``skip``
        logs + counts the lost group and lets the epoch continue (the
        consumer sees fewer rows, never wrong ones); ``fail`` says no."""
        from photon_ml_tpu.resilience.retry import RetryBudgetExceeded

        if self.config.epoch_policy != "skip" or not isinstance(
            err, RetryBudgetExceeded
        ):
            return False
        self.stats.note("decode", 0.0, groups_skipped=1)
        obs.registry().inc("ingest.pipeline.groups_skipped")
        obs.emit_event(
            "io.pipeline.group_skipped",
            cat="io",
            chunk=index,
            files=self.groups[index],
            error=repr(err),
        )
        return True

    def parts(self) -> Iterator[dict]:
        """Ordered iterator of decoded columnar parts (one per file
        group). Decode runs on a thread pool, bounded so it never gets
        more than ``prefetch_depth`` parts (plus one in flight per
        worker) ahead of the consumer; consumer-side waits are counted
        as pipeline stalls. A group whose retries exhaust follows
        ``epoch_policy`` (fail the epoch, or skip-and-log the group)."""
        groups = self.groups
        nworkers = min(self.decode_workers, len(groups))
        if nworkers <= 1 and len(groups) == 1:
            try:
                yield self._decode_group(0, groups[0])
            except BaseException as e:  # noqa: BLE001 — policy gate
                if not self._skip_group(0, e):
                    raise
            return
        cond = threading.Condition()
        results: Dict[int, Tuple[str, object]] = {}
        state = {"next_to_take": 0, "consumed": 0, "cancel": False}
        budget = self.config.prefetch_depth + nworkers

        def worker():
            while True:
                with cond:
                    while True:
                        if state["cancel"]:
                            return
                        i = state["next_to_take"]
                        if i >= len(groups):
                            return
                        # bounded producer: stay within `budget` of the
                        # consumer so decoded chunks don't pile up
                        if i - state["consumed"] < budget:
                            state["next_to_take"] = i + 1
                            break
                        cond.wait(0.05)
                try:
                    out = ("ok", self._decode_group(i, groups[i]))
                except BaseException as e:  # noqa: BLE001 — reraised below
                    out = ("error", e)
                with cond:
                    results[i] = out
                    cond.notify_all()

        threads = [
            threading.Thread(
                target=worker, name=f"ingest-decode-{t}", daemon=True
            )
            for t in range(nworkers)
        ]
        for t in threads:
            t.start()
        reg = obs.registry()
        try:
            for i in range(len(groups)):
                with cond:
                    if i not in results:
                        t0 = time.perf_counter()
                        while i not in results:
                            cond.wait()
                        dt = time.perf_counter() - t0
                        self.stats.note_stall(dt)
                        reg.inc("ingest.pipeline.stalls")
                        reg.observe(
                            "ingest.pipeline.stall_ms", dt * 1e3
                        )
                    kind, payload = results.pop(i)
                    state["consumed"] = i + 1
                    cond.notify_all()
                if kind == "error":
                    if self._skip_group(i, payload):
                        continue
                    raise payload
                yield payload
        finally:
            with cond:
                state["cancel"] = True
                cond.notify_all()
            for t in threads:
                t.join(timeout=10.0)

    # -- stage 2: uniform-row staging --------------------------------------

    def chunks(
        self,
        vocab_index: int = 0,
        dtype=np.float64,
        rows_per_chunk: Optional[int] = None,
        pad_tail: bool = False,
        ring: Optional[_StagingRing] = None,
    ) -> Iterator[StagedChunk]:
        """Decoded parts -> uniform ``rows_per_chunk`` row blocks staged
        in the preallocated ring (dense features + scalar columns, cast
        to ``dtype``). With ``pad_tail`` the final partial block is
        zero-padded to the uniform shape with its mask zeroed (the
        out-of-core path wants ONE compiled shape); otherwise the tail
        keeps its real row count (the deposit path writes exact rows)."""
        vocab = self.vocabs[vocab_index]
        d = len(vocab)
        rpc = rows_per_chunk or rows_per_chunk_for(
            self.config.chunk_mb, d, np.dtype(dtype).itemsize
        )
        if ring is None:
            ring = _StagingRing(self.config.prefetch_depth + 1)
        index = 0
        start_row = 0
        slot = -1
        buf: Optional[Dict[str, np.ndarray]] = None
        fill = 0

        def start_block():
            nonlocal slot, buf, fill
            slot, buf = ring.acquire(rpc, d, dtype)
            fill = 0

        names_cache: Dict[int, List[str]] = {}

        def chunk_names(coll) -> List[str]:
            limit = min(d, coll.max_features)
            if limit not in names_cache:
                names = []
                for j in range(limit):
                    name, term = vocab.name_term(j)
                    names.append(f"{name}{term}" if term else str(name))
                names_cache[limit] = names
            return names_cache[limit]

        def emit(rows: int) -> StagedChunk:
            nonlocal index, start_row
            # quality fingerprint: sketch the staged rows HERE, while
            # they are host-resident numpy (the streamed/out-of-core
            # paths never hold an in-core batch to sketch later); the
            # sketch aggregates copy immediately, so ring-slot reuse
            # after transfer cannot corrupt them
            coll = _quality.fingerprint_collector()
            if coll is not None:
                coll.observe_batch(
                    buf["features"][:rows],
                    buf["labels"][:rows],
                    buf["weights"][:rows],
                    shard="features",
                    names=chunk_names(coll),
                )
            if pad_tail and rows < rpc:
                buf["features"][rows:] = 0.0
                for k in ("labels", "offsets", "weights"):
                    buf[k][rows:] = 0.0
            buf["mask"][:rows] = 1.0
            if pad_tail:
                buf["mask"][rows:] = 0.0
            out = StagedChunk(
                index=index,
                start_row=start_row,
                rows=rows,
                features=(
                    buf["features"]
                    if pad_tail or rows == rpc
                    else buf["features"][:rows]
                ),
                labels=buf["labels"] if pad_tail or rows == rpc else buf["labels"][:rows],
                offsets=buf["offsets"] if pad_tail or rows == rpc else buf["offsets"][:rows],
                weights=buf["weights"] if pad_tail or rows == rpc else buf["weights"][:rows],
                mask=buf["mask"] if pad_tail or rows == rpc else buf["mask"][:rows],
                ring_slot=slot,
            )
            index += 1
            start_row += rows
            return out

        start_block()
        for part in self.parts():
            n = part["n"]
            if n == 0:
                continue
            t0 = time.perf_counter()
            with obs.span("ingest.stage", cat="io", rows=n):
                dense = _with_watchdog(
                    lambda: _dense_part(part, vocab, vocab_index),
                    self.config.stage_timeout_s,
                    "stage",
                    f"{n} rows",
                )
                cols = {
                    "labels": part["labels"],
                    "offsets": part["offsets"],
                    "weights": part["weights"],
                }
                off = 0
                while off < n:
                    take = min(rpc - fill, n - off)
                    np.copyto(
                        buf["features"][fill : fill + take],
                        dense[off : off + take],
                        casting="unsafe",
                    )
                    for k, src in cols.items():
                        np.copyto(
                            buf[k][fill : fill + take],
                            src[off : off + take],
                            casting="unsafe",
                        )
                    fill += take
                    off += take
                    if fill == rpc:
                        self.stats.note(
                            "stage",
                            time.perf_counter() - t0,
                            t0=t0,
                            chunks=1,
                        )
                        obs.registry().inc("ingest.pipeline.chunks")
                        yield emit(rpc)
                        t0 = time.perf_counter()
                        start_block()
            self.stats.note("stage", time.perf_counter() - t0, t0=t0)
        if fill > 0:
            self.stats.note("stage", 0.0, chunks=1)
            obs.registry().inc("ingest.pipeline.chunks")
            yield emit(fill)
        self._ring = ring  # keep the ring alive until the pipeline dies

    # -- stage 3: async device transfer ------------------------------------

    def device_chunks(
        self,
        vocab_index: int = 0,
        dtype=None,
        rows_per_chunk: Optional[int] = None,
        pad_tail: bool = False,
    ):
        """Staged chunks -> device-resident chunks, transfer one chunk
        ahead of the consumer (double buffering: chunk N+1's
        ``device_put`` is issued before chunk N is yielded, so its
        copy — and the decode/staging behind it — overlaps whatever the
        consumer does with chunk N)."""
        import jax.numpy as jnp

        out_dtype = np.dtype(dtype or jnp.float32)
        ring = _StagingRing(self.config.prefetch_depth + 1)
        gen = self.chunks(
            vocab_index=vocab_index,
            dtype=out_dtype,
            rows_per_chunk=rows_per_chunk,
            pad_tail=pad_tail,
            ring=ring,
        )
        pending = None
        for staged in gen:
            dev = self._transfer(staged, ring)
            if pending is not None:
                yield pending
            pending = dev
        if pending is not None:
            yield pending

    def _transfer(self, staged: StagedChunk, ring: _StagingRing):
        from photon_ml_tpu.resilience import retry as _retry

        t0 = time.perf_counter()
        nbytes = sum(
            a.nbytes
            for a in (
                staged.features,
                staged.labels,
                staged.offsets,
                staged.weights,
                staged.mask,
            )
        )

        def copy_once():
            # chaos seam: the host->device transfer stage. The staged
            # ring slot is still owned by this chunk until the copies
            # complete, so a retried transfer re-reads intact buffers.
            _faults.fire("pipeline.transfer", key=str(staged.index))
            return {
                "features": _owned_device_copy(staged.features),
                "labels": _owned_device_copy(staged.labels),
                "offsets": _owned_device_copy(staged.offsets),
                "weights": _owned_device_copy(staged.weights),
                "mask": _owned_device_copy(staged.mask),
            }

        def copy_attempt():
            attempts["n"] += 1
            return _with_watchdog(
                copy_once,
                self.config.stage_timeout_s,
                "transfer",
                f"chunk {staged.index}",
            )

        attempts = {"n": 0}
        with obs.span(
            "ingest.transfer", cat="io", chunk=staged.index, bytes=nbytes
        ):
            dev = _retry.retry_call(
                copy_attempt,
                retries=2,
                base_delay=0.02,
                max_delay=0.25,
                label=f"pipeline transfer chunk {staged.index}",
            )
        if attempts["n"] > 1:
            self.stats.note("transfer", 0.0, retries=attempts["n"] - 1)
        ring.note_transfer(staged.ring_slot, tuple(dev.values()))
        dt = time.perf_counter() - t0
        self.stats.note("transfer", dt, t0=t0, bytes_to_device=nbytes)
        reg = obs.registry()
        reg.inc("ingest.pipeline.bytes_to_device", nbytes)
        reg.observe("ingest.pipeline.transfer_ms", dt * 1e3)
        return {
            "index": staged.index,
            "start_row": staged.start_row,
            "rows": staged.rows,
            **dev,
        }

    # -- assembly entry points ---------------------------------------------

    def labeled_batch(self, vocab_index: int = 0, dtype=None):
        """-> (LabeledBatch, uids, label_present): the full dataset
        assembled ON DEVICE from the pipelined chunks via the
        destructive deposit — bit-for-bit equal to the one-shot
        ``IngestSource.labeled_batch`` on the same files (drilled in
        tests/test_pipeline.py). Device peak: dataset + one in-flight
        chunk (``hbm_watermark("io.ingest.assemble")``)."""
        import jax.numpy as jnp

        out_dtype = dtype or jnp.float32
        t_start = time.perf_counter()
        uids_parts: List[np.ndarray] = []
        present_parts: List[np.ndarray] = []
        dev_chunks = []

        # tee the host metadata off the decoded parts while the staged
        # chunks stream to the device
        orig_parts = self.parts

        def parts_with_meta():
            for part in orig_parts():
                uids_parts.append(part["uids"])
                present_parts.append(part["label_present"])
                yield part

        self.parts = parts_with_meta  # type: ignore[method-assign]
        try:
            for dev in self.device_chunks(
                vocab_index=vocab_index, dtype=out_dtype
            ):
                dev_chunks.append(dev)
        finally:
            self.parts = orig_parts  # type: ignore[method-assign]
        total = sum(c["rows"] for c in dev_chunks)
        if total == 0:
            raise ValueError(f"no records found in {self.files}")
        d = len(self.vocabs[vocab_index])
        t0 = time.perf_counter()
        with obs.hbm_watermark("io.ingest.assemble"):
            batch = deposit_batch(dev_chunks, total, d, out_dtype)
        self.stats.note("consume", time.perf_counter() - t0, t0=t0)
        self.stats.finish(time.perf_counter() - t_start)
        uids = np.concatenate(uids_parts)
        present = np.concatenate(present_parts)
        return batch, uids, present

    def read_columnar(self) -> dict:
        """The pipeline-parallel equivalent of
        ``native.read_columnar(files, vocabs, ...)``: identical output
        dict (labels/offsets/weights/uids/entities/coo per vocab, n),
        decoded by the bounded pool instead of one unbounded map — the
        GAME ingest path (``IngestSource.game_data_streamed``)."""
        t_start = time.perf_counter()
        parts = list(self.parts())
        out = (
            parts[0]
            if len(parts) == 1
            else _merge_parts(parts, self.entity_keys, len(self.vocabs))
        )
        self.stats.finish(time.perf_counter() - t_start)
        return out


def _merge_parts(
    parts: List[dict], entity_keys: Sequence[str], nvocabs: int
) -> dict:
    """Concatenate decoded parts in order; COO row ids shift by the
    running row total (the same merge as ``native.read_columnar``)."""
    n = sum(p["n"] for p in parts)
    row_base = np.cumsum([0] + [p["n"] for p in parts])[:-1]
    coo = []
    for vi in range(nvocabs):
        rows = np.concatenate(
            [
                p["coo"][vi][0].astype(np.int64) + base
                for p, base in zip(parts, row_base)
            ]
        )
        cols = np.concatenate([p["coo"][vi][1] for p in parts])
        vals = np.concatenate([p["coo"][vi][2] for p in parts])
        coo.append((rows, cols, vals))
    return {
        "n": n,
        "labels": np.concatenate([p["labels"] for p in parts]),
        "label_present": np.concatenate([p["label_present"] for p in parts]),
        "offsets": np.concatenate([p["offsets"] for p in parts]),
        "weights": np.concatenate([p["weights"] for p in parts]),
        "uids": np.concatenate([p["uids"] for p in parts]),
        "entities": {
            k: np.concatenate([p["entities"][k] for p in parts])
            for k in entity_keys
        },
        "coo": coo,
    }


# ---------------------------------------------------------------------------
# device-side deposit (the PR-4 destructive assemble, generalized)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _deposit_fn():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def _deposit(buf, chunk, off):
        zero = jnp.zeros((), off.dtype)
        idx = (off,) + (zero,) * (buf.ndim - 1)
        return jax.lax.dynamic_update_slice(buf, chunk, idx)

    return _deposit


def deposit_chunks(chunks: List, total: int, width: Optional[int] = None):
    """Preallocated-buffer assembly via donated ``dynamic_update_slice``
    (the PR-4 destructive ``assemble()``): each chunk's device buffer
    becomes collectible the moment its deposit is enqueued, so the
    device peak is the dataset plus ONE in-flight chunk — a
    ``jnp.concatenate`` would hold 2x alive. ``chunks`` is consumed
    DESTRUCTIVELY (pop + release)."""
    import jax.numpy as jnp

    deposit = _deposit_fn()
    shape = (total,) if width is None else (total, width)
    buf = jnp.zeros(shape, chunks[0].dtype)
    off = 0
    while chunks:
        c = chunks.pop(0)
        # off rides as a traced scalar: one compile per chunk SHAPE,
        # not per offset
        buf = deposit(buf, c, jnp.asarray(off, jnp.int32))
        off += c.shape[0]
        del c  # last host reference; the device buffer frees
    return buf


def deposit_batch(dev_chunks: List[dict], total: int, d: int, dtype):
    """Device chunk dicts -> one assembled LabeledBatch. Chunk lists are
    consumed destructively field-by-field, widest first, so the peak
    stays dataset + one chunk."""
    from photon_ml_tpu.core.types import LabeledBatch

    feats = [c["features"] for c in dev_chunks]
    labels = [c["labels"] for c in dev_chunks]
    offsets = [c["offsets"] for c in dev_chunks]
    weights = [c["weights"] for c in dev_chunks]
    masks = [c["mask"] for c in dev_chunks]
    dev_chunks.clear()
    features = deposit_chunks(feats, total, d)
    return LabeledBatch(
        features=features,
        labels=deposit_chunks(labels, total),
        offsets=deposit_chunks(offsets, total),
        weights=deposit_chunks(weights, total),
        mask=deposit_chunks(masks, total),
    )


# ---------------------------------------------------------------------------
# out-of-core epochs: StreamedDesign + StreamingObjective
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamedDesign:
    """A host-resident chunked dataset for out-of-core training: the
    design exceeds HBM, so each objective pass STREAMS the uniform
    chunks host->device (transfer double-buffered against compute) and
    accumulates exact partials. All chunks share one padded shape
    (``rows_per_chunk``, d) — padding rows carry mask 0, so they are
    algebraically invisible to every masked reduction."""

    chunks: List[Dict[str, np.ndarray]]
    n: int
    d: int
    rows_per_chunk: int
    dtype: object

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)

    @property
    def bytes_per_epoch(self) -> int:
        return sum(
            sum(a.nbytes for a in c.values()) for c in self.chunks
        )

    @staticmethod
    def from_pipeline(
        pipeline: IngestPipeline,
        vocab_index: int = 0,
        dtype=np.float64,
        rows_per_chunk: Optional[int] = None,
    ) -> "StreamedDesign":
        """Decode (parallel) + stage (uniform, padded) once; keep the
        chunks host-side. The staged ring views are COPIED — the ring
        is reused under the iterator."""
        d = len(pipeline.vocabs[vocab_index])
        out: List[Dict[str, np.ndarray]] = []
        n = 0
        rpc = None
        for staged in pipeline.chunks(
            vocab_index=vocab_index,
            dtype=dtype,
            rows_per_chunk=rows_per_chunk,
            pad_tail=True,
        ):
            rpc = staged.features.shape[0]
            n += staged.rows
            out.append(
                {
                    "features": staged.features.copy(),
                    "labels": staged.labels.copy(),
                    "offsets": staged.offsets.copy(),
                    "weights": staged.weights.copy(),
                    "mask": staged.mask.copy(),
                }
            )
        if not out:
            raise ValueError(f"no records found in {pipeline.files}")
        return StreamedDesign(
            chunks=out, n=n, d=d, rows_per_chunk=rpc, dtype=np.dtype(dtype)
        )

    @staticmethod
    def from_batch(batch, rows_per_chunk: int) -> "StreamedDesign":
        """Split an in-core dense LabeledBatch into an out-of-core
        design (tests / benches: the equivalence oracle)."""
        feats = np.asarray(batch.features)
        if feats.ndim != 2:
            raise ValueError("StreamedDesign requires dense features")
        n, d = feats.shape
        cols = {
            "labels": np.asarray(batch.labels),
            "offsets": np.asarray(batch.offsets),
            "weights": np.asarray(batch.weights),
            "mask": np.asarray(batch.mask),
        }
        dtype = feats.dtype
        chunks = []
        for lo in range(0, n, rows_per_chunk):
            hi = min(lo + rows_per_chunk, n)
            rows = hi - lo
            c = {
                "features": np.zeros((rows_per_chunk, d), dtype),
                "labels": np.zeros((rows_per_chunk,), dtype),
                "offsets": np.zeros((rows_per_chunk,), dtype),
                "weights": np.zeros((rows_per_chunk,), dtype),
                "mask": np.zeros((rows_per_chunk,), dtype),
            }
            c["features"][:rows] = feats[lo:hi]
            for k in cols:
                c[k][:rows] = cols[k][lo:hi]
            chunks.append(c)
        return StreamedDesign(
            chunks=chunks,
            n=n,
            d=d,
            rows_per_chunk=rows_per_chunk,
            dtype=dtype,
        )


@functools.lru_cache(maxsize=16)
def _streaming_passes(loss, dtype_str: str):
    """jitted per-chunk partial passes + the donated-carry accumulator.
    One compilation per (loss, dtype) x chunk shape — the l2/l1 terms
    stay OUTSIDE (pure functions of w, added once per sweep), so every
    lambda of a regularization path shares these executables (the
    in-core objective's own GLMObjective methods)."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.core.types import LabeledBatch
    from photon_ml_tpu.ops.objective import GLMObjective

    obj = GLMObjective(loss=loss)

    def batch_of(c):
        return LabeledBatch(
            features=c["features"],
            labels=c["labels"],
            offsets=c["offsets"],
            weights=c["weights"],
            mask=c["mask"],
        )

    def vg_pass(w, c):
        val, grad, _ = obj.value_grad_curvature(w, batch_of(c))
        return val, grad

    def hv_pass(w, v, c):
        batch = batch_of(c)
        curv = obj.hessian_coefficients(w, batch)
        return obj.hessian_vector_at(curv, v, batch)

    def diag_pass(w, c):
        return obj.hessian_diagonal(w, batch_of(c))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def acc(carry, delta):
        return jax.tree_util.tree_map(jnp.add, carry, delta)

    return (
        jax.jit(vg_pass),
        jax.jit(hv_pass),
        jax.jit(diag_pass),
        acc,
    )


class StreamingObjective:
    """The exact full-dataset GLM objective over a :class:`StreamedDesign`,
    evaluated one chunk at a time: each call streams every chunk
    host->device (chunk i+1's transfer issued before chunk i's pass —
    the double buffer), runs the fused per-chunk partial pass, and folds
    the partials into a DONATED carry, then adds the L2 term once. The
    row sums are the same sums the in-core :class:`GLMObjective`
    computes (value/grad/HVP/diag are all plain row sums — no means), so
    the only difference from in-core is floating-point reassociation
    across chunk boundaries.

    ``value_and_grad`` / ``hessian_vector`` are TRACE-SAFE: inside a
    solver's ``lax.while_loop`` they run through ``jax.pure_callback``,
    so the unmodified TRON/L-BFGS/OWL-QN loops drive out-of-core epochs
    without knowing it (models.training.train_glm_streamed)."""

    def __init__(
        self,
        design: StreamedDesign,
        loss,
        l2_weight: float = 0.0,
        stats: Optional[PipelineStats] = None,
    ):
        self.design = design
        self.loss = loss
        self.l2_weight = float(l2_weight)
        self.stats = stats if stats is not None else PipelineStats()
        self._vg, self._hv, self._diag, self._acc = _streaming_passes(
            loss, str(np.dtype(design.dtype))
        )

    # -- chunk transfer -----------------------------------------------------

    def _put(self, i: int):
        import jax

        c = self.design.chunks[i]
        t0 = time.perf_counter()
        dev = {k: jax.device_put(v) for k, v in c.items()}
        dt = time.perf_counter() - t0
        nbytes = sum(v.nbytes for v in c.values())
        self.stats.note("transfer", dt, t0=t0, bytes_to_device=nbytes)
        return dev

    def _sweep(self, kind: str, pass_fn, *w_args):
        """One out-of-core epoch: stream every chunk through ``pass_fn``
        accumulating partials in the donated carry. Transfers run one
        chunk ahead of compute."""
        import jax

        design = self.design
        t0 = time.perf_counter()
        with obs.span(
            "ingest.oocore.sweep",
            cat="io",
            kind=kind,
            chunks=design.num_chunks,
        ), jax.disable_jit(False):
            # disable_jit(False): train_glm_streamed runs the solver
            # loops host-side under disable_jit (see its rationale);
            # the per-chunk passes must still be the COMPILED fused
            # programs — one executable per chunk shape, not an op
            # soup per sweep
            w_dev = tuple(jax.device_put(np.asarray(a)) for a in w_args)
            nxt = self._put(0)
            carry = None
            for i in range(design.num_chunks):
                cur = nxt
                if i + 1 < design.num_chunks:
                    # double buffer: issue the NEXT transfer before this
                    # chunk's pass so copy and compute overlap
                    nxt = self._put(i + 1)
                tc0 = time.perf_counter()
                partial = pass_fn(*w_dev, cur)
                carry = (
                    partial if carry is None else self._acc(carry, partial)
                )
                self.stats.note(
                    "consume", time.perf_counter() - tc0, t0=tc0
                )
        wall = time.perf_counter() - t0
        self.stats.finish(wall)
        reg = obs.registry()
        reg.inc("ingest.oocore.sweeps")
        reg.inc(f"ingest.oocore.sweeps.{kind}")
        reg.observe("ingest.oocore.sweep_ms", wall * 1e3)
        return carry

    # -- host-side (eager) evaluations --------------------------------------

    def _host_value_and_grad(self, w):
        val, grad = self._sweep("value_and_grad", self._vg, w)
        return (
            np.asarray(val, self.design.dtype),
            np.asarray(grad, self.design.dtype),
        )

    def _host_hessian_vector(self, w, v):
        hv = self._sweep("hessian_vector", self._hv, w, v)
        return np.asarray(hv, self.design.dtype)

    def hessian_diagonal(self, w):
        """diag(H) + l2 (eager; feeds coefficient variances)."""
        diag = np.asarray(self._sweep("hessian_diagonal", self._diag, w))
        return diag + self.l2_weight

    # -- trace-safe entry points (the solver surface) ------------------------

    def value_and_grad(self, w):
        """Full-dataset (value, grad), callable inside jit/while_loop:
        the chunk sweep runs on the host via ``jax.pure_callback``; the
        L2 term is added in-trace (a pure function of w needs no
        streaming)."""
        import jax
        import jax.numpy as jnp

        dt = np.dtype(self.design.dtype)
        val, grad = jax.pure_callback(
            self._host_value_and_grad,
            (
                jax.ShapeDtypeStruct((), dt),
                jax.ShapeDtypeStruct((self.design.d,), dt),
            ),
            w,
        )
        if self.l2_weight:
            val = val + 0.5 * self.l2_weight * jnp.vdot(w, w)
            grad = grad + self.l2_weight * w
        return val, grad

    def hessian_vector(self, w, v):
        """Full-dataset H(w) @ v, callable inside jit/while_loop."""
        import jax

        dt = np.dtype(self.design.dtype)
        hv = jax.pure_callback(
            self._host_hessian_vector,
            jax.ShapeDtypeStruct((self.design.d,), dt),
            w,
            v,
        )
        if self.l2_weight:
            hv = hv + self.l2_weight * v
        return hv
