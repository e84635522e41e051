"""Entity-sharded serving: mesh-partitioned RE tables + shard routing.

The unsharded :class:`~photon_ml_tpu.serving.engine.ScoringEngine` keeps
one ENTIRE compact random-effect table resident per process, so serving
capacity is bounded by a single device's HBM while the rest of the mesh
idles. This module is the serving analog of PR 14's entity-sharded GAME
descent — "one mesh per model" instead of "one replica per model":

- **Ownership = the checkpoint rule.** Entity -> shard follows the SAME
  round-robin rule as sharded checkpoints and entity-sharded training
  (``io.checkpoint.shard_rows`` via ``game.data.entity_shard_assignment``)
  — device layout, checkpoint layout, and request routing all derive
  from one rule and cannot drift.
- **Shard-routed micro-batches.** :func:`route_batch` groups a batch's
  rows by owning shard (the serving analog of
  ``game.data.entity_partition_rows``): each shard's sub-batch pads to
  ONE shared power-of-two bucket, so routed traffic rides the same AOT
  bucket ladder as unsharded serving — zero steady-state recompiles. A
  request whose entities span shards (e.g. userId on shard 0, itemId on
  shard 2) places on EVERY owner shard; partial scores merge host-side
  in ascending-shard order with the fixed-effect contribution applied
  exactly once (on the primary = lowest owner shard).
- **Zero cross-shard collectives.** Scoring is one ``shard_map``'d
  program per bucket: each shard gathers from ITS table block and dots
  ITS sub-batch; the compiled HLO contains NO collective instructions
  (asserted in tests). Only the final per-request merge of the (P,
  bucket) partials crosses shards — as a host-side sum of a few floats
  per request.
- **Sharded loading.** :func:`load_sharded_re_table` assembles a
  serving shard set directly from a PR-11 sharded checkpoint
  (``step-<N>/shard-<p>-of-<P>.npz`` + quorum manifest), one checkpoint
  shard file at a time — the full dense (E, d) table is never
  materialized, and the serving shard count is free to differ from the
  checkpoint's.

Fault site ``serving.shard_route`` (key = shard index) is probed once
per shard per routed batch: a raise/corrupt-mode fault marks that shard
DOWN for the batch — its entities degrade to fixed-effect-only scores
(cold-start semantics, the same answer the tiered cache gives a miss)
and every request still completes. Zero lost requests, honest p99.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.game.data import (
    EntityShardAssignment,
    entity_shard_assignment,
)
from photon_ml_tpu.game.scoring import (
    CompactReTable,
    _factored_scores,
    _fixed_scores,
    _random_scores_compact_dense,
    compact_table_rows,
    precompact_model,
    shard_compact_table,
)
from photon_ml_tpu.resilience import faults as _faults
from photon_ml_tpu.serving.engine import (
    ScoringEngine,
    _ScorePlan,
    bucket_size,
)

__all__ = [
    "ShardedCompactTable",
    "RoutedBatch",
    "route_batch",
    "ShardedScoringEngine",
    "load_sharded_re_table",
    "iter_checkpoint_re_blocks",
]


@dataclasses.dataclass(frozen=True)
class ShardedCompactTable:
    """A compact RE table ALREADY in the stored (shard-major, padded)
    layout of ``assignment`` — what the sharded-checkpoint loader
    produces, and what :class:`ShardedScoringEngine` pins directly
    (skipping the global compact -> stored reshuffle)."""

    columns: np.ndarray  # (padded_rows, k) int32, shard-major
    values: np.ndarray  # (padded_rows, k)
    assignment: EntityShardAssignment


# ---------------------------------------------------------------------------
# shard routing (the serving analog of game.data.entity_partition_rows)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RoutedBatch:
    """One batch's rows grouped by owning shard.

    Placements: each (row, shard) pair where the row has work on that
    shard — its primary placement (fixed effect + every RE coordinate
    owned there) plus one placement per ADDITIONAL owner shard of its
    entities. Sorted by (row, shard), so the merge adds partial scores
    in ascending-shard order per request — deterministic.
    """

    num_rows: int
    num_shards: int
    bucket: int
    p_row: np.ndarray  # (M,) original batch row of each placement
    p_shard: np.ndarray  # (M,) owner shard of each placement
    p_slot: np.ndarray  # (M,) slot within the shard's padded sub-batch
    fixed_mask: np.ndarray  # (M,) 1.0 on the primary placement
    ents: Dict[str, np.ndarray]  # re_key -> (M,) shard-LOCAL ids (-1 off)
    counts: np.ndarray  # (P,) placements per shard
    down_shards: Tuple[int, ...]  # shards degraded by a routing fault
    degraded_rows: int  # placements whose RE gathers were dropped

    def scatter_feats(
        self, features: Dict[str, np.ndarray], dtype
    ) -> Dict[str, np.ndarray]:
        """(B, d) per shard-name -> routed (P, bucket, d); pad slots
        stay zero (they score 0 and carry fixed_mask 0)."""
        out = {}
        for name, x in features.items():
            x = np.asarray(x, dtype)
            routed = np.zeros(
                (self.num_shards, self.bucket) + x.shape[1:], dtype
            )
            routed[self.p_shard, self.p_slot] = x[self.p_row]
            out[name] = routed
        return out

    def routed_entities(self) -> Dict[str, np.ndarray]:
        """Shard-local entity ids as routed (P, bucket) int32 (-1 on pad
        slots and on placements that don't own the key)."""
        out = {}
        for rk, e in self.ents.items():
            routed = np.full(
                (self.num_shards, self.bucket), -1, np.int32
            )
            routed[self.p_shard, self.p_slot] = e
            out[rk] = routed
        return out

    def routed_fixed_mask(self, dtype) -> np.ndarray:
        routed = np.zeros((self.num_shards, self.bucket), dtype)
        routed[self.p_shard, self.p_slot] = self.fixed_mask
        return routed

    def merge(self, partials: np.ndarray) -> np.ndarray:
        """(P, bucket) per-shard partial scores -> (B,) per-request
        scores: the ONE step that crosses shards, summed host-side in
        placement order (ascending shard within each request)."""
        t0 = time.perf_counter()
        with obs.span(
            "serving.route.merge",
            cat="serving",
            rows=self.num_rows,
            shards=self.num_shards,
        ):
            out = np.zeros(self.num_rows, partials.dtype)
            np.add.at(
                out, self.p_row, partials[self.p_shard, self.p_slot]
            )
        obs.registry().observe(
            "serving.route.merge_ms", (time.perf_counter() - t0) * 1e3
        )
        return out


def route_batch(
    entity_ids: Dict[str, Optional[np.ndarray]],
    assignments: Dict[str, EntityShardAssignment],
    num_rows: int,
    num_shards: int,
    min_bucket: int = 8,
) -> RoutedBatch:
    """Group ``num_rows`` batch rows by owning shard.

    A row's primary shard is the LOWEST shard owning any of its known
    entities (all-cold rows spread round-robin by row index — they score
    fixed-effect-only, so any shard balances); additional owner shards
    get secondary placements carrying only the RE keys they own. Probes
    ``serving.shard_route`` once per involved shard; a raise/corrupt
    fault marks the shard down (its RE gathers degrade to -1).

    The host-side routing cost is decomposed into ``serving.route.{group,pad}``
    spans + ``_ms`` histograms here (``serving.route.merge`` lives on
    :meth:`RoutedBatch.merge`) so ROADMAP item 2's dispatch-free attack
    has a measured per-stage baseline."""
    t_group = time.perf_counter()
    owner: Dict[str, np.ndarray] = {}
    local: Dict[str, np.ndarray] = {}
    for rk, a in assignments.items():
        o = np.full(num_rows, -1, np.int64)
        l = np.full(num_rows, -1, np.int64)
        e = entity_ids.get(rk)
        if e is not None:
            e = np.asarray(e, np.int64)
            known = (e >= 0) & (e < a.num_entities)
            o[known] = a.owner_of_global(e[known])
            l[known] = a.local_of_global(e[known])
        owner[rk] = o
        local[rk] = l

    rows = np.arange(num_rows, dtype=np.int64)
    if owner:
        own_mat = np.stack([owner[rk] for rk in sorted(owner)])
        primary = np.where(own_mat >= 0, own_mat, num_shards).min(axis=0)
    else:
        primary = np.full(num_rows, num_shards, np.int64)
    cold = primary >= num_shards
    primary[cold] = rows[cold] % num_shards

    flat = [rows * num_shards + primary]
    for rk in sorted(owner):
        known = owner[rk] >= 0
        flat.append(rows[known] * num_shards + owner[rk][known])
    flat = np.unique(np.concatenate(flat))  # sorted => (row, shard) order
    p_row = flat // num_shards
    p_shard = (flat % num_shards).astype(np.int64)
    fixed_mask = (p_shard == primary[p_row]).astype(np.float64)

    # chaos seam: per-shard routing. raise/corrupt = shard down for this
    # batch (entities degrade to fixed-effect-only, zero lost requests);
    # delay = a slow route leg (the tail-latency drill).
    down: List[int] = []
    for s in np.unique(p_shard).tolist():
        try:
            action = _faults.fire("serving.shard_route", key=str(s))
        except OSError:
            down.append(int(s))
        else:
            if action.corrupt:
                down.append(int(s))
    down_mask = np.isin(p_shard, down) if down else np.zeros(
        p_shard.shape, bool
    )

    ents: Dict[str, np.ndarray] = {}
    for rk in sorted(owner):
        e = np.full(p_row.shape, -1, np.int32)
        sel = (owner[rk][p_row] == p_shard) & ~down_mask
        e[sel] = local[rk][p_row[sel]].astype(np.int32)
        ents[rk] = e
    t_pad = time.perf_counter()

    counts = np.bincount(p_shard, minlength=num_shards)
    bucket = bucket_size(max(int(counts.max()), 1), min_bucket)
    order = np.argsort(p_shard, kind="stable")  # keeps (row, shard) order
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.empty(p_row.shape, np.int64)
    slot[order] = np.arange(p_row.size) - starts[p_shard[order]]

    t_end = time.perf_counter()
    reg = obs.registry()
    reg.observe("serving.route.group_ms", (t_pad - t_group) * 1e3)
    reg.observe("serving.route.pad_ms", (t_end - t_pad) * 1e3)
    tracer = obs.get_tracer()
    if tracer is not None:
        # retro-emitted stage spans (the batcher's serving.request idiom):
        # group = ownership lookup + placements + fault probes + RE ids,
        # pad = bucket sizing + slot assignment. Retro add_span bypasses
        # the ambient-context merge obs.span does, so the batch identity
        # (the trace join key — docs/OBSERVABILITY.md) rides explicitly.
        ctx = obs.current_span_context() or {}
        ctx_args = (
            {"batch_id": ctx["batch_id"]} if "batch_id" in ctx else {}
        )
        end_us = tracer.now_us()
        pad_us = (t_end - t_pad) * 1e6
        group_us = (t_pad - t_group) * 1e6
        tracer.add_span(
            "serving.route.group", end_us - pad_us - group_us, group_us,
            cat="serving", args={"rows": int(num_rows),
                                 "placements": int(p_row.size),
                                 **ctx_args},
        )
        tracer.add_span(
            "serving.route.pad", end_us - pad_us, pad_us,
            cat="serving", args={"bucket": int(bucket), **ctx_args},
        )

    return RoutedBatch(
        num_rows=num_rows,
        num_shards=num_shards,
        bucket=bucket,
        p_row=p_row,
        p_shard=p_shard,
        p_slot=slot,
        fixed_mask=fixed_mask,
        ents=ents,
        counts=counts,
        down_shards=tuple(down),
        degraded_rows=int(np.count_nonzero(down_mask)),
    )


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ShardedScoringEngine(ScoringEngine):
    """Mesh-partitioned serving engine: RE table rows shard round-robin
    over an 'entity' device mesh; batches route per shard and score as
    one ``shard_map``'d per-shard gather+dot with zero cross-shard
    collectives. Per-process resident RE bytes drop ~P x at P shards
    (the ``serving.shard.resident_re_bytes_per_process`` gauge).

    Same construction surface as :class:`ScoringEngine` plus
    ``num_shards``; :meth:`from_sharded_checkpoint` stands one up
    straight from a PR-11 sharded checkpoint step without ever holding
    the full dense table."""

    def __init__(
        self,
        params,
        shards,
        random_effects,
        shard_vocabs=None,
        re_vocabs=None,
        *,
        num_shards: int,
        mesh=None,
        **kw,
    ):
        from photon_ml_tpu.parallel.mesh import make_entity_mesh

        if kw.get("hbm_cache_entities"):
            raise ValueError(
                "the tiered HBM/host cache composes with the unsharded "
                "engine; on a sharded mesh each shard's slice IS the "
                "resident set (drop hbm_cache_entities or num_shards)"
            )
        num_shards = int(num_shards)
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if mesh is None:
            ndev = len(jax.devices())
            if num_shards > ndev:
                raise ValueError(
                    f"{num_shards} serving shards need {num_shards} "
                    f"devices, have {ndev}"
                )
            mesh = make_entity_mesh(num_shards)
        self.num_shards = num_shards
        self.mesh = mesh
        self.assignments: Dict[str, EntityShardAssignment] = {}
        super().__init__(
            params, shards, random_effects, shard_vocabs, re_vocabs, **kw
        )

    # -- construction hooks ------------------------------------------------

    def _placement_fingerprint(self) -> str:
        # shard_map'd executables are pinned to this mesh's device set —
        # only engines on the SAME mesh may share them
        return "mesh:" + ",".join(
            str(d.id) for d in self.mesh.devices.flat
        ) + f"/{self.num_shards}"

    def _precompact(self, params):
        pre = {
            n: p
            for n, p in params.items()
            if isinstance(p, ShardedCompactTable)
        }
        out = precompact_model(
            {n: p for n, p in params.items() if n not in pre}
        )
        out.update(pre)
        return out

    def _pin_params(self, compact):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        ent_sharding = lambda nd: NamedSharding(
            self.mesh, P(ENTITY_AXIS, *([None] * (nd - 1)))
        )
        replicated = NamedSharding(self.mesh, P())

        # resolve one assignment per RE key (all coordinates sharing a
        # key index the same entity axis; a pre-sharded table brings its
        # own — they must agree)
        for name in self._coord_order:
            re_key = self.random_effects.get(name)
            if re_key is None:
                continue
            p = compact[name]
            if isinstance(p, ShardedCompactTable):
                a = p.assignment
                if a.num_shards != self.num_shards:
                    raise ValueError(
                        f"coordinate {name!r}: table pre-sharded at "
                        f"{a.num_shards} shards, engine has "
                        f"{self.num_shards}"
                    )
            else:
                rows = int(
                    np.shape(
                        p.gamma if hasattr(p, "gamma") else p.columns
                    )[0]
                )
                a = self.assignments.get(re_key) or entity_shard_assignment(
                    rows, self.num_shards
                )
            prev = self.assignments.setdefault(re_key, a)
            if prev.num_entities != a.num_entities:
                raise ValueError(
                    f"coordinate {name!r}: {a.num_entities} entities, "
                    f"other coordinates keyed {re_key!r} have "
                    f"{prev.num_entities}"
                )

        params: Dict[str, object] = {}
        specs: Dict[str, object] = {}
        re_bytes = 0
        for name in self._coord_order:
            p = compact[name]
            re_key = self.random_effects.get(name)
            if re_key is None:
                params[name] = jax.device_put(
                    jnp.asarray(np.asarray(p, self.dtype)), replicated
                )
                specs[name] = P()
                continue
            a = self.assignments[re_key]
            if hasattr(p, "gamma"):  # FactoredParams: gamma entity-keyed
                stored = a.table_from_global(
                    np.asarray(p.gamma, self.dtype)
                )
                gamma = jax.device_put(
                    jnp.asarray(stored), ent_sharding(2)
                )
                params[name] = type(p)(
                    gamma=gamma,
                    projection=jax.device_put(
                        jnp.asarray(np.asarray(p.projection, self.dtype)),
                        replicated,
                    ),
                )
                specs[name] = type(p)(
                    gamma=P(ENTITY_AXIS, None), projection=P()
                )
                re_bytes += gamma.nbytes // self.num_shards
                continue
            if isinstance(p, ShardedCompactTable):
                cols_np = np.asarray(p.columns, np.int32)
                vals_np = np.asarray(p.values, self.dtype)
            else:  # global CompactReTable -> stored shard-major layout
                stored = shard_compact_table(p, a)
                cols_np = np.asarray(stored.columns, np.int32)
                vals_np = np.asarray(stored.values, self.dtype)
            cols = jax.device_put(jnp.asarray(cols_np), ent_sharding(2))
            vals = jax.device_put(jnp.asarray(vals_np), ent_sharding(2))
            params[name] = CompactReTable(columns=cols, values=vals)
            specs[name] = CompactReTable(
                columns=P(ENTITY_AXIS, None), values=P(ENTITY_AXIS, None)
            )
            re_bytes += (cols.nbytes + vals.nbytes) // self.num_shards
        self._param_specs = specs
        # ONE shard's slice: what each process of a P-process deployment
        # keeps resident (the ~P x drop vs the unsharded engine's gauge)
        self.stats.registry.set_gauge(
            "serving.shard.resident_re_bytes_per_process", re_bytes
        )
        return params

    def _make_scorers(self):
        from jax.sharding import PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        def shard_body(params, feats, ents, fixed_mask):
            # per shard: (1, bucket, ...) routed blocks + this shard's
            # table slice. No collective ops anywhere below — partials
            # leave the program still sharded.
            f = {s: feats[s][0] for s in self._used_shards}
            n = f[self._used_shards[0]].shape[0]
            fixed = jnp.zeros((n,), self.dtype)
            total = jnp.zeros((n,), self.dtype)
            for name in self._coord_order:
                p = params[name]
                ff = f[self.shards[name]]
                re_key = self.random_effects.get(name)
                if re_key is None:
                    fixed = fixed + _fixed_scores(p, ff)
                elif hasattr(p, "gamma"):
                    total = total + _factored_scores(
                        p.gamma, p.projection, ff, ents[re_key][0]
                    )
                else:
                    total = total + _random_scores_compact_dense(
                        p.columns, p.values, ff, ents[re_key][0]
                    )
            return (fixed_mask[0] * fixed + total)[None, :]

        def sharded_scorer(params, feats, ents, fixed_mask):
            in_specs = (
                self._param_specs,
                {
                    s: P(ENTITY_AXIS, None, None)
                    for s in self._used_shards
                },
                {rk: P(ENTITY_AXIS, None) for rk in self._re_keys},
                P(ENTITY_AXIS, None),
            )
            with jax.named_scope("score"):
                return jax.shard_map(
                    shard_body,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=P(ENTITY_AXIS, None),
                    check_vma=False,
                )(params, feats, ents, fixed_mask)

        self._scorer = jax.jit(sharded_scorer)
        self._scorer_fixed = jax.jit(self._score_padded_fixed)

    def _abstract_inputs(self, bucket, dims, fixed_only):
        if fixed_only:
            # degraded mode bypasses routing entirely: plain padded
            # (bucket, d) batches against the replicated fixed params
            return super()._abstract_inputs(bucket, dims, fixed_only)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        sh3 = NamedSharding(self.mesh, P(ENTITY_AXIS, None, None))
        sh2 = NamedSharding(self.mesh, P(ENTITY_AXIS, None))
        feats_s = {
            s: jax.ShapeDtypeStruct(
                (
                    self.num_shards,
                    bucket,
                    dims[s] if dims else self._shard_dim(s),
                ),
                self.dtype,
                sharding=sh3,
            )
            for s in self._used_shards
        }
        ents_s = {
            rk: jax.ShapeDtypeStruct(
                (self.num_shards, bucket), jnp.int32, sharding=sh2
            )
            for rk in self._re_keys
        }
        mask_s = jax.ShapeDtypeStruct(
            (self.num_shards, bucket), self.dtype, sharding=sh2
        )
        return (feats_s, ents_s, mask_s)

    # -- scoring -----------------------------------------------------------

    def _prepare(self, features, entity_ids, fixed_only):
        """The routed batch as the base engine's ``_score`` runs it:
        route rows to their owner shards, scatter the features, place
        them on the mesh; the call is the one sharded program, the finish
        merges the per-shard partials."""
        if fixed_only:
            return super()._prepare(features, entity_ids, True)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        missing = [s for s in self._used_shards if s not in features]
        if missing:
            raise KeyError(f"missing feature shard(s): {missing}")
        n = int(np.shape(features[self._used_shards[0]])[0])
        plan = route_batch(
            {rk: entity_ids.get(rk) for rk in self._re_keys},
            self.assignments,
            n,
            self.num_shards,
            self.min_bucket,
        )
        if plan.down_shards:
            self.stats.record_shard_degraded(
                plan.down_shards, plan.degraded_rows
            )
        # chaos seam shared with the unsharded engine: raise-mode
        # surfaces through the batcher, corrupt-mode poisons scores
        action = _faults.fire("serving.score", key=str(plan.bucket))
        feats_np = {
            s: np.asarray(features[s], self.dtype)
            for s in self._used_shards
        }
        routed = plan.scatter_feats(feats_np, self.dtype)
        compiled = self._ensure_compiled(
            plan.bucket,
            {s: feats_np[s].shape[1] for s in self._used_shards},
        )
        sh3 = NamedSharding(self.mesh, P(ENTITY_AXIS, None, None))
        sh2 = NamedSharding(self.mesh, P(ENTITY_AXIS, None))
        feats_dev = {
            s: jax.device_put(routed[s], sh3) for s in self._used_shards
        }
        ents_dev = {
            rk: jax.device_put(e, sh2)
            for rk, e in plan.routed_entities().items()
        }
        mask_dev = jax.device_put(plan.routed_fixed_mask(self.dtype), sh2)

        def record(elapsed):
            self.stats.record_bucket_latency(plan.bucket, elapsed)
            self.stats.record_shard_batch(plan.counts, elapsed)

        return _ScorePlan(
            rows=n,
            bucket=plan.bucket,
            action=action,
            attrs={"shards": self.num_shards},
            call=lambda: compiled(
                self._params, feats_dev, ents_dev, mask_dev
            ),
            finish=plan.merge,
            record=record,
        )

    def shard_presort_key(self, requests: Sequence[object]) -> np.ndarray:
        """Primary owner shard per request — the MicroBatcher's
        ``presort_fn`` so routed sub-batches come out contiguous (the
        serving analog of applying ``entity_partition_rows`` once)."""
        keys = np.full(len(requests), self.num_shards, np.int64)
        for i, r in enumerate(requests):
            best = self.num_shards
            for rk, a in self.assignments.items():
                raw = getattr(r, "entities", {}).get(rk)
                if raw is None:
                    continue
                vocab = self.re_vocabs.get(rk, {})
                e = vocab.get(raw)
                if e is None:
                    from photon_ml_tpu.io.models import _maybe_int

                    e = vocab.get(_maybe_int(raw))
                if e is not None and 0 <= e < a.num_entities:
                    best = min(
                        best, int(a.owner_of_global(np.asarray([e]))[0])
                    )
            keys[i] = best if best < self.num_shards else i % self.num_shards
        return keys

    # -- sharded-checkpoint construction -----------------------------------

    @classmethod
    def from_sharded_checkpoint(
        cls,
        step_dir: str,
        shards: Dict[str, str],
        random_effects: Dict[str, Optional[str]],
        shard_vocabs=None,
        *,
        num_shards: int,
        **kw,
    ) -> "ShardedScoringEngine":
        """Stand up a sharded engine from one PR-11 sharded checkpoint
        step (``step-<N>/`` with quorum manifest). Entity-sharded tables
        stream in one checkpoint shard file at a time
        (:func:`load_sharded_re_table`); the serving shard count may
        differ from the checkpoint's. Entity vocabularies come from the
        manifest's global entity-key order, so restored rows attach to
        the right entities at ANY width (the PR-4 lesson)."""
        manifest = _read_step_manifest(step_dir)
        kinds = manifest.get("param_kinds", {})
        sharding = manifest.get("param_sharding", {})
        params: Dict[str, object] = {}
        re_vocabs: Dict[str, dict] = {}
        shard0 = None
        for name, re_key in random_effects.items():
            if name not in manifest.get("param_names", []):
                raise ValueError(
                    f"coordinate {name!r} not in checkpoint "
                    f"{step_dir!r} (has {manifest.get('param_names')})"
                )
            if kinds.get(name) == "factored":
                raise ValueError(
                    f"coordinate {name!r}: factored params load through "
                    "the export path, not the sharded checkpoint loader"
                )
            if re_key is None or sharding.get(name) != "entity":
                if shard0 is None:
                    shard0 = _load_shard_npz(step_dir, 0)
                params[name] = np.asarray(shard0[f"param/{name}"])
                continue
            table, ekeys = load_sharded_re_table(
                step_dir, name, num_shards
            )
            params[name] = table
            vocab = {k: i for i, k in enumerate(ekeys)}
            prev = re_vocabs.setdefault(re_key, vocab)
            if prev != vocab:
                raise ValueError(
                    f"coordinates keyed {re_key!r} disagree on the "
                    "checkpoint's entity order"
                )
        return cls(
            params,
            shards,
            random_effects,
            shard_vocabs,
            re_vocabs,
            num_shards=num_shards,
            **kw,
        )


# ---------------------------------------------------------------------------
# sharded-checkpoint streaming loader
# ---------------------------------------------------------------------------


def _read_step_manifest(step_dir: str) -> dict:
    path = os.path.join(step_dir, "manifest.json")
    with open(path, encoding="utf-8") as f:
        manifest = json.load(f)
    if manifest.get("format") != "sharded":
        raise ValueError(f"{step_dir!r} is not a sharded checkpoint step")
    return manifest


def _load_shard_npz(step_dir: str, p: int):
    manifest = _read_step_manifest(step_dir)
    num = int(manifest["shards"])
    return np.load(os.path.join(step_dir, f"shard-{p}-of-{num}.npz"))


def iter_checkpoint_re_blocks(step_dir: str, name: str):
    """Yield ``(global_rows, block)`` per checkpoint shard file for one
    entity-sharded table — one file resident at a time (the streaming
    seam ``load_sharded_re_table`` consumes). Row ownership re-derives
    from the shared round-robin rule, so it holds at any width."""
    from photon_ml_tpu.io.checkpoint import shard_rows

    manifest = _read_step_manifest(step_dir)
    num = int(manifest["shards"])
    ekeys = manifest.get("entity_keys", {}).get(name)
    if not ekeys:
        raise ValueError(
            f"coordinate {name!r} is not entity-sharded in {step_dir!r}"
        )
    e = len(ekeys)
    for p in range(num):
        npz = np.load(os.path.join(step_dir, f"shard-{p}-of-{num}.npz"))
        key = f"param/{name}"
        if key not in npz:
            continue
        rows = np.asarray(list(shard_rows(e, p, num)), np.int64)
        yield rows, np.asarray(npz[key])


def load_sharded_re_table(
    step_dir: str,
    name: str,
    num_shards: int,
    k: Optional[int] = None,
    only_shard: Optional[int] = None,
) -> Tuple[object, List[str]]:
    """Assemble one coordinate's serving shard set straight from a PR-11
    sharded checkpoint — WITHOUT materializing the full dense (E, d)
    table: each checkpoint shard block compacts independently at a
    shared width ``k`` (two streaming passes: max-nnz scan, then fill).
    Returns ``(ShardedCompactTable, entity_keys)`` in the manifest's
    global entity order; with ``only_shard`` the compact arrays cover
    just that serving shard's block (what one process of a P-process
    deployment loads — peak memory O(E/P))."""
    manifest = _read_step_manifest(step_dir)
    ekeys = manifest.get("entity_keys", {}).get(name)
    if not ekeys:
        raise ValueError(
            f"coordinate {name!r} is not entity-sharded in {step_dir!r}"
        )
    e = len(ekeys)
    assignment = entity_shard_assignment(e, num_shards)
    if k is None:
        k = 1
        for _, block in iter_checkpoint_re_blocks(step_dir, name):
            if block.size:
                nnz = (block != 0).sum(axis=1)
                k = max(k, int(nnz.max()) if nnz.size else 1)
    lo, hi = 0, assignment.padded_rows
    if only_shard is not None:
        lo = only_shard * assignment.rows_per_shard
        hi = lo + assignment.rows_per_shard
    cols = None
    vals = None
    for rows, block in iter_checkpoint_re_blocks(step_dir, name):
        if vals is None:
            cols = np.zeros((hi - lo, k), np.int32)
            vals = np.zeros((hi - lo, k), block.dtype)
        stored = assignment.global_to_stored[rows]
        keep = (stored >= lo) & (stored < hi)
        if not np.any(keep):
            continue
        bc, bv = compact_table_rows(block[keep], k)
        cols[stored[keep] - lo] = bc
        vals[stored[keep] - lo] = bv
    if vals is None:
        raise ValueError(
            f"no shard file of {step_dir!r} carries coordinate {name!r}"
        )
    return (
        ShardedCompactTable(
            columns=cols, values=vals, assignment=assignment
        ),
        [str(key) for key in ekeys],
    )
