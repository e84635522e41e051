"""GAME data layer: struct-of-arrays batches + per-entity bucketing.

Rebuild of the reference's L5 (``data/GameDatum.scala:32``,
``data/FixedEffectDataSet.scala``, ``data/RandomEffectDataSet.scala:39-381``,
``data/LocalDataSet.scala``). A GAME dataset here is:

  - feature shards: dict shard_id -> dense (n, d_shard) matrix, or a
    padded-ELL ``ops.sparse.SparseFeatures`` for wide shards (the
    reference's featureShardContainer, one Breeze vector per row per
    shard — sparse Breeze vectors map to the ELL container). Sparse
    shards serve FIXED-EFFECT coordinates; per-entity designs need the
    dense row gather and reject them.
  - response/offset/weight columns (n,)
  - entity columns: dict random_effect_id -> (n,) int32 entity indices
    (index -1 = entity unseen at vocabulary build; scores 0 like the
    reference's missing-entity cogroup)

Random-effect training data is bucketed ONCE at ingest into padded
(num_entities, rows_cap, d) tensors (`RandomEffectDesign`) — the TPU analog
of RandomEffectDataSet's groupByKey + reservoir capping + partitioner
placement. Rows beyond the cap stay out of the active tensors but are still
scored through the coefficient table (the reference's passive data,
``RandomEffectDataSet.scala:319-358``).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Collection, Dict, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.core.types import LabeledBatch, _pytree_dataclass
from photon_ml_tpu.ops.bucketing import split_minimizing_padding


@dataclasses.dataclass
class GameData:
    """Host-side container for a scored dataset (plain arrays, not a pytree;
    device placement happens per coordinate)."""

    features: Dict[str, np.ndarray]  # shard -> (n, d_shard)
    labels: np.ndarray  # (n,)
    offsets: np.ndarray  # (n,)
    weights: np.ndarray  # (n,)
    entity_ids: Dict[str, np.ndarray]  # re_name -> (n,) int32, -1 = unknown

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @staticmethod
    def create(
        features: Mapping[str, np.ndarray],
        labels,
        offsets=None,
        weights=None,
        entity_ids: Optional[Mapping[str, np.ndarray]] = None,
    ) -> "GameData":
        from photon_ml_tpu.ops.sparse import is_hybrid, is_structured

        labels = np.asarray(labels, np.float64)
        n = labels.shape[0]
        for name, v in {**features, **(entity_ids or {})}.items():
            if is_hybrid(v):
                # hybrid rows are permuted relative to every other column;
                # GAME joins shards/entities/scores BY ROW
                raise ValueError(
                    f"shard {name!r} is a HybridFeatures container; GAME "
                    "shards must be dense or plain ELL (row-aligned)"
                )
            rows = v.shape[0] if is_structured(v) else np.shape(v)[0]
            if rows != n:
                raise ValueError(
                    f"column {name!r} has {rows} rows, labels have {n}"
                )
        return GameData(
            features={
                k: (v if is_structured(v) else np.asarray(v))
                for k, v in features.items()
            },
            labels=labels,
            offsets=(
                np.zeros(n) if offsets is None else np.asarray(offsets, np.float64)
            ),
            weights=(
                np.ones(n) if weights is None else np.asarray(weights, np.float64)
            ),
            entity_ids={
                k: np.asarray(v, np.int32)
                for k, v in (entity_ids or {}).items()
            },
        )

    def fixed_effect_batch(self, shard: str, dtype=jnp.float32) -> LabeledBatch:
        """(n, d) LabeledBatch view for a fixed-effect coordinate
        (``data/FixedEffectDataSet.scala:31``)."""
        return LabeledBatch.create(
            self.features[shard],
            self.labels,
            offsets=self.offsets,
            weights=self.weights,
            dtype=dtype,
        )


@_pytree_dataclass
class RandomEffectDesign:
    """Padded per-entity active training tensors for one random effect.

    features: (E, R, d)   labels/weights/mask: (E, R)
    row_index: (E, R) int32 — global row each active slot came from (-1 pad),
    used to gather per-row residual offsets each coordinate pass without
    re-bucketing.
    """

    features: jax.Array
    labels: jax.Array
    weights: jax.Array
    mask: jax.Array
    row_index: jax.Array

    @property
    def num_entities(self) -> int:
        return self.features.shape[0]

    @property
    def rows_per_entity(self) -> int:
        return self.features.shape[1]

    @property
    def dim(self) -> int:
        return self.features.shape[2]

    def gather_offsets(self, full_offsets: jax.Array) -> jax.Array:
        """(n,) -> (E, R): route each row's current residual offset to its
        active slot. The reference does this with an RDD join per pass
        (``data/RandomEffectDataSet.scala:58-75``); here it is one gather.

        The plain DEFINITION, an index a padded slot. The coordinates
        deliver the same values through :func:`gather_offsets_compact`,
        an index a held row."""
        safe = jnp.maximum(self.row_index, 0)
        return jnp.take(full_offsets, safe, axis=0) * self.mask


def offsets_gather_maps(buckets):
    """Static maps of :func:`gather_offsets_compact` over the buckets of
    one random effect, from each bucket's host ``(row_index, mask)``:
    ``(perm, starts)``, int32.

    ``perm`` lists the rows to gather bucket by bucket, SLOT-major: slot
    j of a bucket contributes the rows its lanes ``lo_j .. hi_j`` hold
    there, ``lo_j`` / ``hi_j`` the first and last lane holding slot j.
    Lanes in an order monotone in their row count (the bucketed builder's,
    and a shard's block of it) make every such range exactly the lanes
    that hold the slot, so ``perm`` has one entry a held row; a lane
    inside the range that does not reach the slot costs one wasted index
    (row 0, masked), which is all an unordered design pays. ``starts[b]``
    is (R_b,): slot j's range, read as ``E_b`` consecutive entries of the
    gathered vector padded by ``max_b E_b`` at both ends, starting at
    ``starts[b][j]``, puts lane e's value at position e.

    Raises where a lane's held slots are not a prefix of its slots (what
    ``_fill_design`` builds) or a held slot names no row."""
    buckets = [(np.asarray(ri), np.asarray(m) > 0) for ri, m in buckets]
    pad = max(held.shape[0] for _, held in buckets)
    perm, starts, position = [], [], pad
    for b, (row_index, held) in enumerate(buckets):
        holes = (held[:, 1:] & ~held[:, :-1]).any(axis=1)
        if holes.any():
            raise ValueError(
                f"bucket {b}: the held slots of lane "
                f"{int(np.flatnonzero(holes)[0])} are not a prefix of its "
                "slots; the offsets gather needs every lane's rows in "
                "slots 0 .. count - 1"
            )
        if np.any(row_index[held] < 0):
            raise ValueError(
                f"bucket {b}: a slot the mask holds has no row (row_index -1)"
            )
        lo, hi, in_range = _slot_ranges(held)
        perm.append(np.maximum(row_index.T[in_range], 0))
        first = position + np.cumsum(hi - lo) - (hi - lo)
        starts.append((first - lo).astype(np.int32))
        position += int(np.sum(hi - lo))
    return np.concatenate(perm).astype(np.int32), tuple(starts)


def _slot_ranges(held):
    """``(lo, hi, in_range)`` of one bucket's (E_b, R_b) held mask: slot j's
    range of lanes ``lo[j] .. hi[j] - 1``, the first and last lane holding
    it, and ``in_range`` (R_b, E_b), whose entries in row-major order are
    the bucket's entries of ``perm``."""
    lanes = held.shape[0]
    held_t = held.T  # (R_b, E_b)
    some = held_t.any(axis=1)
    lo = np.where(some, held_t.argmax(axis=1), 0)
    hi = np.where(some, lanes - held_t[:, ::-1].argmax(axis=1), 0)
    lane = np.arange(lanes)
    return lo, hi, (lane >= lo[:, None]) & (lane < hi[:, None])


def held_slot_values(arrays, masks):
    """Each bucket's (E_b, R_b) host array of ``arrays`` read at the
    entries of :func:`offsets_gather_maps`' ``perm``, in its order (a wasted
    entry of an unordered design reads a slot the mask does not hold)."""
    return np.concatenate([
        np.asarray(a).T[_slot_ranges(np.asarray(m) > 0)[2]]
        for a, m in zip(arrays, masks)
    ])


def gather_offsets_compact(full_offsets, maps, masks):
    """``[bucket.gather_offsets(full_offsets) for bucket in buckets]``, the
    same values slot for slot, with ``maps = offsets_gather_maps(...)`` of
    those buckets and ``masks`` their (E_b, R_b) masks: ONE gather of an
    index a held row, then every slot of every bucket filled by one
    contiguous run of the gathered vector (what a run reads beyond the
    lanes that hold its slot lands on masked slots). XLA's gather on the
    TPU is paid by the index, not the byte, and a run by the run, not the
    lane: about 7 ns an index and 1 to 2 us a run, at most a bucket's
    depth of them (PERF.md section 6, PR 35); about half of a bucketed
    design's padded slots hold no row."""
    perm, starts = maps
    return fill_offsets(gather_held_offsets(full_offsets, perm), starts,
                        masks)


def gather_held_offsets(full_offsets, perm):
    """The gather of :func:`gather_offsets_compact`: the residual offset of
    every entry of ``perm`` (one a held row of an ordered design), in
    ``perm``'s order. A factored coordinate's shared-projection solve reads
    this vector as it is; :func:`fill_offsets` spreads it over the padded
    slots."""
    return jnp.take(full_offsets, perm, axis=0, mode="clip")


def fill_offsets(gathered, starts, masks):
    """The fills of :func:`gather_offsets_compact`: every slot of every
    bucket from one contiguous run of ``gathered``."""
    pad = max(m.shape[0] for m in masks)
    gathered = jnp.pad(gathered, (pad, pad))
    out = []
    for start, mask in zip(starts, masks):
        lanes = mask.shape[0]
        runs = jax.vmap(
            lambda at: jax.lax.dynamic_slice_in_dim(gathered, at, lanes)
        )(start)  # (R_b, E_b)
        out.append(runs.T * mask)
    return out


def spread_lanes(lanes, starts, masks, size):
    """The inverse of :func:`fill_offsets`: every bucket's per-lane values
    ``lanes[b]`` (E_b, k) laid over the entries of
    :func:`offsets_gather_maps`' ``perm``, in its order, as (size, k),
    ``size`` at least the entries, zeros past them. An entry holds its
    lane's value: what a gather of the lanes' table rows by each entry's
    entity reads, with no table in between.

    Slot j of bucket b is one run of entries, lanes ``lo_j .. hi_j - 1``
    (the first and last lane holding it, read off the mask); it is
    written by one lanes-wide window at ``starts[b][j]``, selected to that
    lane range, so that windows that overlap leave every entry its own
    lane's value. A wasted entry of an unordered design takes its lane's
    value too. Built (k, .), entries minor, like the held rows' features:
    a run then writes k contiguous rows of the bucket's width."""
    pad = max(m.shape[0] for m in masks)
    k = lanes[0].shape[1]
    out = jnp.zeros((k, size + 2 * pad), lanes[0].dtype)
    for values, start, mask in zip(lanes, starts, masks):
        held = mask > 0  # (E_b, R_b)
        width = held.shape[0]
        some = held.any(axis=0)
        lo = jnp.where(some, jnp.argmax(held, axis=0), 0)
        hi = jnp.where(some, width - jnp.argmax(held[::-1], axis=0), 0)
        lane = jnp.arange(width)
        columns = values.T  # (k, E_b)

        def put(j, out):
            at = start[j]
            window = jax.lax.dynamic_slice_in_dim(out, at, width, axis=1)
            own = (lane >= lo[j]) & (lane < hi[j])
            return jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(own, columns, window), at, axis=1)

        out = jax.lax.fori_loop(0, start.shape[0], put, out)
    return jax.lax.slice_in_dim(out, pad, pad + size, axis=1).T


def _grouped_rows(eids: np.ndarray, seed: int):
    """Vectorized per-entity grouping with a uniform random shuffle inside
    each entity (the reservoir-sample analog; no Python per-entity loop).

    Returns (order, sorted_ids, slot, uniq, counts): `order` are row indices
    sorted by (entity, random), `slot` is each row's position within its
    entity, `uniq`/`counts` the entities present and their row counts.
    """
    rng = np.random.default_rng(seed)
    rand = rng.uniform(size=eids.shape[0])
    order = np.lexsort((rand, eids))
    sorted_ids = eids[order]
    valid = sorted_ids >= 0
    order, sorted_ids = order[valid], sorted_ids[valid]
    uniq, starts, counts = np.unique(
        sorted_ids, return_index=True, return_counts=True
    )
    slot = np.arange(order.size) - np.repeat(starts, counts)
    return order, sorted_ids, slot, uniq, counts


def _fill_design(
    data: GameData,
    shard: str,
    rows: np.ndarray,
    ent_rows: np.ndarray,
    slot_rows: np.ndarray,
    rescale_rows: np.ndarray,
    shape_e: int,
    cap: int,
    dtype,
) -> RandomEffectDesign:
    """Scatter kept rows into padded (shape_e, cap, d) tensors."""
    x = np.asarray(data.features[shard])
    d = x.shape[1]
    # in the rows' own type: the cast below gives the same values as a
    # detour through float64, at half the bytes for float32 rows
    feats = np.zeros((shape_e, cap, d), x.dtype)
    labels = np.zeros((shape_e, cap), np.float64)
    weights = np.zeros((shape_e, cap), np.float64)
    mask = np.zeros((shape_e, cap), np.float64)
    row_index = np.full((shape_e, cap), -1, np.int64)
    feats[ent_rows, slot_rows] = x[rows]
    labels[ent_rows, slot_rows] = data.labels[rows]
    weights[ent_rows, slot_rows] = data.weights[rows] * rescale_rows
    mask[ent_rows, slot_rows] = 1.0
    row_index[ent_rows, slot_rows] = rows
    return RandomEffectDesign(
        features=jnp.asarray(feats, dtype),
        labels=jnp.asarray(labels, dtype),
        weights=jnp.asarray(weights, dtype),
        mask=jnp.asarray(mask, dtype),
        row_index=jnp.asarray(row_index, jnp.int32),
    )


def pearson_correlation_scores(
    features: np.ndarray, labels: np.ndarray, mask: np.ndarray
) -> np.ndarray:
    """(E, R, d) design -> (E, d) per-entity Pearson |correlation| basis.

    Vectorized rebuild of ``LocalDataSet.computePearsonCorrelationScore``
    (``LocalDataSet.scala:198-259``): per entity, corr(feature_j, label)
    over its active rows; a present feature with ~zero variance is the
    intercept — the FIRST such gets score 1.0, later ones 0.0; features
    absent from the entity's rows score -inf (never selected).
    """
    m = mask > 0
    x = np.where(m[:, :, None], features, 0.0)
    y = np.where(m, labels, 0.0)
    n = m.sum(axis=1).astype(np.float64)[:, None]  # (E, 1)
    s1 = x.sum(axis=1)
    s2 = (x * x).sum(axis=1)
    sxy = (x * y[:, :, None]).sum(axis=1)
    ly = y.sum(axis=1)[:, None]
    lyy = (y * y).sum(axis=1)[:, None]
    numerator = n * sxy - s1 * ly
    feat_var = np.abs(n * s2 - s1 * s1)
    std = np.sqrt(feat_var)
    label_var = np.maximum(n * lyy - ly * ly, 0.0)
    denominator = std * np.sqrt(label_var)
    # constant labels: correlation is undefined, and a tiny-denominator
    # guard would amplify cancellation noise into garbage scores — force 0.
    # Thresholds are RELATIVE to the moment magnitudes (absolute epsilons
    # break under catastrophic cancellation at large n / large values).
    label_const = label_var < 1e-9 * np.maximum(n * lyy, 1.0)
    score = np.where(
        label_const, 0.0, numerator / (denominator + 1e-12)
    )

    present = s2 > 0.0
    constant = present & (feat_var < 1e-9 * np.maximum(n * s2, 1.0))
    # first constant (intercept-like) feature per entity scores 1.0
    first_const = constant & (
        np.cumsum(constant, axis=1) == 1
    )
    score = np.where(constant, 0.0, score)
    score = np.where(first_const, 1.0, score)
    return np.where(present, np.abs(score), -np.inf)


def filter_features_by_support(
    design: RandomEffectDesign, min_support: int
) -> RandomEffectDesign:
    """Per-entity support filter (``LocalDataSet.filterFeaturesBySupport``,
    ``LocalDataSet.scala:80-109``): a feature survives for an entity iff
    it is STORED (nonzero here — the dense analog of activeKeysIterator)
    in at least ``min_support`` of that entity's active rows. Dropped
    columns are zeroed so their coefficients solve to exactly 0. The
    cheap pre-filter the reference offers ahead of the Pearson ranking.

    Known divergence, by design: the reference counts EXPLICITLY-STORED
    entries (a stored 0.0 adds support there); the dense projected design
    cannot distinguish a stored zero from an absent one, so value != 0 is
    the storedness proxy. Entities whose features carry explicit zeros in
    the source Avro may keep fewer columns here. Exact parity would
    require threading the ELL padding mask through the projection."""
    if min_support <= 0:
        return design
    feats = np.asarray(design.features)
    mask = np.asarray(design.mask) > 0
    support = ((feats != 0.0) & mask[:, :, None]).sum(axis=1)  # (E, d)
    keep = support >= min_support
    return dataclasses.replace(
        design,
        features=jnp.asarray(
            np.where(keep[:, None, :], feats, 0.0), design.features.dtype
        ),
    )


def select_features_by_pearson(
    design: RandomEffectDesign, ratio: float
) -> RandomEffectDesign:
    """Per-entity feature selection: keep the top ceil(ratio * n_e)
    features by |Pearson corr|, zeroing the rest in the design so their
    coefficients solve to exactly 0 (the dense-rep analog of
    ``RandomEffectDataSet.featureSelectionOnActiveData``,
    ``RandomEffectDataSet.scala:360-380``)."""
    if ratio <= 0:
        raise ValueError(f"feature ratio must be positive, got {ratio}")
    feats = np.asarray(design.features, np.float64)
    mask = np.asarray(design.mask)
    score = pearson_correlation_scores(
        feats, np.asarray(design.labels, np.float64), mask
    )
    e, _, d = feats.shape
    n_e = (mask > 0).sum(axis=1)
    k_e = np.minimum(np.ceil(ratio * n_e).astype(np.int64), d)
    rank = np.argsort(np.argsort(-score, axis=1, kind="stable"), axis=1)
    keep = rank < k_e[:, None]  # (E, d)
    return dataclasses.replace(
        design,
        features=jnp.asarray(
            np.where(keep[:, None, :], feats, 0.0), design.features.dtype
        ),
    )


def build_random_effect_design(
    data: GameData,
    random_effect: str,
    shard: str,
    num_entities: int,
    active_cap: Optional[int] = None,
    seed: int = 0,
    dtype=jnp.float32,
    feature_ratio: Optional[float] = None,
    min_support: int = 0,
) -> RandomEffectDesign:
    """Group rows by entity into padded tensors (host-side, once per run).

    Semantics from ``RandomEffectDataSet.buildWithConfiguration``:
      - at most `active_cap` active rows per entity, chosen uniformly at
        random (the reference's reservoir sample, :247-308);
      - sampled rows get weight * count/cap so each entity's total active
        weight is preserved (:299-302);
      - rows of entities with index -1 (unknown) are dropped;
      - `num_entities` fixes the leading axis = the coefficient-table size.

    One global row cap means one hot entity inflates padding for all; use
    :func:`build_bucketed_random_effect_design` when entity sizes are skewed.
    """
    from photon_ml_tpu.ops.sparse import is_structured

    if is_structured(data.features[shard]):
        raise ValueError(
            f"random effect {random_effect!r}: per-entity designs gather "
            f"dense rows; shard {shard!r} is sparse (sparse shards serve "
            "fixed-effect coordinates only)"
        )
    eids = np.asarray(data.entity_ids[random_effect])
    if active_cap is not None and active_cap <= 0:
        raise ValueError(f"active_cap must be positive, got {active_cap}")
    order, sorted_ids, slot, uniq, counts = _grouped_rows(eids, seed)

    max_count = int(counts.max()) if counts.size else 1
    cap = min(max_count, active_cap) if active_cap is not None else max_count

    cap_of = np.minimum(counts, cap)
    keep = slot < np.repeat(cap_of, counts)
    rescale = np.repeat(np.where(counts > cap, counts / cap, 1.0), counts)
    design = _fill_design(
        data,
        shard,
        order[keep],
        sorted_ids[keep],
        slot[keep],
        rescale[keep],
        num_entities,
        cap,
        dtype,
    )
    # support filter first, Pearson ranking second (the reference's
    # LocalDataSet order: the cheap count-based cut precedes the ranking)
    design = filter_features_by_support(design, min_support)
    if feature_ratio is not None:
        design = select_features_by_pearson(design, feature_ratio)
    return design


@dataclasses.dataclass
class BucketedRandomEffectDesign:
    """Size-bucketed padded designs for one random effect.

    Entities are grouped by row count into a few buckets, each padded only
    to ITS max count — the TPU analog of the reference's load-balanced
    entity placement (``data/RandomEffectIdPartitioner.scala:65-99``): the
    greedy bin-pack balanced per-partition work; here the same skew problem
    is solved by making padding local to a size class, so one hot entity no
    longer inflates every entity's padded rows.

    buckets[b] tensors have shape (E_b, R_b, d); entity_index[b] maps bucket
    lane -> row of the global (num_entities, d) coefficient table. Lanes
    padded for entity-axis sharding carry sentinel `num_entities`, which
    gathers clip and scatters drop.
    """

    buckets: list  # List[RandomEffectDesign]
    entity_index: list  # List[np.ndarray (E_b,) int32]
    num_entities: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def dim(self) -> int:
        return self.buckets[0].dim

    @property
    def active_slots(self) -> int:
        """Total padded (entity, row) slots across buckets — the memory and
        FLOP footprint a global-cap design would inflate."""
        return sum(b.num_entities * b.rows_per_entity for b in self.buckets)


def build_bucketed_random_effect_design(
    data: GameData,
    random_effect: str,
    shard: str,
    num_entities: int,
    num_buckets: int = 4,
    active_cap: Optional[int] = None,
    entity_multiple: int = 1,
    seed: int = 0,
    dtype=jnp.float32,
    feature_ratio: Optional[float] = None,
    min_support: int = 0,
) -> BucketedRandomEffectDesign:
    """Like :func:`build_random_effect_design` but with per-size-class row
    caps. Entities (those with data) are sorted by ACTIVE row count (the
    count, or `active_cap` where the count passes it) and split into
    `num_buckets` contiguous groups; each bucket's row cap is its own max
    active count (rows beyond `active_cap` are passive, with the same
    weight-preserving rescale). `entity_multiple` pads each bucket's
    entity axis up to a multiple (the entity-mesh-axis size) so buckets
    shard evenly.

    The whole build is one ``game.design`` span whose attributes are the
    design's own counts, and feeds the ``game.re.capped_entities`` /
    ``game.re.passive_rows`` counters."""
    with obs.span(
        "game.design", cat="data", random_effect=random_effect
    ) as sp:
        design, counted = _build_bucketed_design(
            data, random_effect, shard, num_entities,
            num_buckets=num_buckets, active_cap=active_cap,
            entity_multiple=entity_multiple, seed=seed, dtype=dtype,
            feature_ratio=feature_ratio, min_support=min_support,
        )
        sp.set(
            buckets=design.num_buckets,
            active_slots=design.active_slots,
            bucket_caps=[b.rows_per_entity for b in design.buckets],
            **counted,
        )
    obs.registry().inc("game.re.capped_entities", counted["capped_entities"])
    obs.registry().inc("game.re.passive_rows", counted["passive_rows"])
    return design


class _BucketPlan(NamedTuple):
    """Which rows a bucketed design holds, and where: every kept (active)
    row's bucket, lane and slot, its weight rescale under the cap; every
    bucket's depth and lane -> entity map (sentinel ``num_entities`` on the
    pads of ``entity_multiple``); the host-side counts of the
    ``game.design`` span."""

    rows: np.ndarray
    buckets: np.ndarray
    lanes: np.ndarray
    slots: np.ndarray
    rescale: np.ndarray
    caps: list
    entity_index: list
    counted: dict


def _bucket_plan(eids, num_entities, *, num_buckets, active_cap,
                 entity_multiple, seed) -> _BucketPlan:
    """The grouping, reservoir sample, size split and lane placement that
    every bucketed design shares (dense rows or compact sparse ones)."""
    if active_cap is not None and active_cap <= 0:
        raise ValueError(f"active_cap must be positive, got {active_cap}")
    if entity_multiple <= 0:
        raise ValueError(f"entity_multiple must be positive, got {entity_multiple}")
    order, sorted_ids, slot, uniq, counts = _grouped_rows(eids, seed)

    if uniq.size == 0:
        # no rows with a known entity: one all-masked bucket so callers
        # (initial_params, update) keep working, like the global builder
        empty = np.asarray([], np.int64)
        return _BucketPlan(
            empty, empty, empty, empty, np.asarray([]), [1],
            [np.full(entity_multiple, num_entities, np.int32)],
            dict(entities=0, active_rows=0, passive_rows=0,
                 capped_entities=0),
        )

    # per-entity active cap under the bucket policy. The split sees what a
    # bucket will hold: an entity over the cap pads like one AT the cap, so
    # splitting on raw counts would spend buckets on sizes the cap removes
    active_counts = (
        counts if active_cap is None else np.minimum(counts, active_cap)
    )
    by_count = np.argsort(active_counts, kind="stable")
    splits = split_minimizing_padding(active_counts[by_count], num_buckets)
    splits = [by_count[lo:hi] for lo, hi in splits]

    cap_of_entity = np.zeros(num_entities, np.int64)
    bucket_of_entity = np.full(num_entities, -1, np.int64)
    local_of_entity = np.zeros(num_entities, np.int64)
    bucket_caps = []
    entity_index = []
    for b, split in enumerate(splits):
        ents = uniq[split]
        cmax = int(counts[split].max())
        cap_b = min(cmax, active_cap) if active_cap is not None else cmax
        bucket_caps.append(cap_b)
        cap_of_entity[ents] = np.minimum(counts[split], cap_b)
        bucket_of_entity[ents] = b
        local_of_entity[ents] = np.arange(ents.size)
        e_pad = -(-ents.size // entity_multiple) * entity_multiple
        idx = np.full(e_pad, num_entities, np.int64)
        idx[: ents.size] = ents
        entity_index.append(np.asarray(idx, np.int32))

    keep = slot < cap_of_entity[sorted_ids]
    full_count = np.zeros(num_entities, np.int64)
    full_count[uniq] = counts
    rescale_of_entity = np.where(
        full_count > cap_of_entity,
        full_count / np.maximum(cap_of_entity, 1),
        1.0,
    )

    rows = order[keep]
    ents = sorted_ids[keep]
    return _BucketPlan(
        rows=rows,
        buckets=bucket_of_entity[ents],
        lanes=local_of_entity[ents],
        slots=slot[keep],
        rescale=rescale_of_entity[ents],
        caps=bucket_caps,
        entity_index=entity_index,
        counted=dict(
            entities=int(uniq.size),
            active_rows=int(rows.size),
            passive_rows=int(order.size - rows.size),
            capped_entities=int(np.sum(counts > active_counts)),
        ),
    )


def _build_bucketed_design(
    data, random_effect, shard, num_entities, *, num_buckets, active_cap,
    entity_multiple, seed, dtype, feature_ratio, min_support,
):
    """(design, its host-side counts: entities with rows, active and
    passive rows, entities over the cap) of
    :func:`build_bucketed_random_effect_design`."""
    from photon_ml_tpu.ops.sparse import is_structured

    if is_structured(data.features[shard]):
        raise ValueError(
            f"random effect {random_effect!r}: per-entity designs gather "
            f"dense rows; shard {shard!r} is sparse (a sparse shard's "
            "random effect is built by build_index_map_design)"
        )
    plan = _bucket_plan(
        np.asarray(data.entity_ids[random_effect]), num_entities,
        num_buckets=num_buckets, active_cap=active_cap,
        entity_multiple=entity_multiple, seed=seed,
    )
    buckets = []
    for b, (cap_b, idx) in enumerate(zip(plan.caps, plan.entity_index)):
        sel = plan.buckets == b
        bucket = _fill_design(
            data,
            shard,
            plan.rows[sel],
            plan.lanes[sel],
            plan.slots[sel],
            plan.rescale[sel],
            idx.size,
            cap_b,
            dtype,
        )
        bucket = filter_features_by_support(bucket, min_support)
        if feature_ratio is not None:
            bucket = select_features_by_pearson(bucket, feature_ratio)
        buckets.append(bucket)

    return BucketedRandomEffectDesign(
        buckets=buckets, entity_index=plan.entity_index,
        num_entities=num_entities,
    ), plan.counted


# a bucket's compact width is a whole number of these blocks of columns:
# the lanes' products read a local id as (block, lane of the block)
# (``game.coordinates._lane_matvec``)
COMPACT_BLOCK = 128


@_pytree_dataclass
class CompactEllBucket:
    """One bucket of a random effect over a SPARSE shard, each lane in its
    own entity's compact column space (INDEX_MAP,
    ``IndexMapProjectorRDD.scala:113-120``): the rows' stored entries as
    local column ids into the lane's coefficient vector of the bucket's
    width ``k_b``, never a dense k axis.

    columns: (E_b, s, R_b) int32 local ids in [0, k_b) (0 on a pad)
    values:  (E_b, s, R_b) their values (0 on a pad), s the shard's slots
    labels / weights / mask / row_index: (E_b, R_b) as
    :class:`RandomEffectDesign`'s.

    The slots lie ahead of the rows, so that the long axis is minor on the
    chip (an (R, s) pair of axes with s = 8 minor lies padded to 128 lanes,
    16 times its size)."""

    columns: jax.Array
    values: jax.Array
    labels: jax.Array
    weights: jax.Array
    mask: jax.Array
    row_index: jax.Array

    @property
    def num_entities(self) -> int:
        return self.columns.shape[0]

    @property
    def rows_per_entity(self) -> int:
        return self.columns.shape[2]


@dataclasses.dataclass
class IndexMapDesign:
    """A random effect over a sparse shard, bucketed as
    :func:`build_bucketed_random_effect_design` buckets its entities, with
    the rows in each lane's compact columns (:class:`CompactEllBucket`) and
    the coefficients in ONE flat ragged table laid out by ``index_map``
    (``game.projectors.RaggedIndexMap``): bucket b's lanes are an
    (E_b, k_b) block of it, k_b the largest union of the bucket's lanes.

    ``row_slots`` / ``row_values`` (s, n) are every row's stored entries as
    positions of the flat table (active and passive rows alike; an entry
    outside its entity's union, or of a row whose entity is unknown, has
    value 0): scoring a row is a gather of the table at its slots."""

    buckets: list  # List[CompactEllBucket]
    entity_index: list  # List[np.ndarray (E_b,) int32]
    index_map: object  # game.projectors.RaggedIndexMap
    row_slots: jax.Array
    row_values: jax.Array
    num_entities: int

    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    @property
    def active_slots(self) -> int:
        """Padded (entity, row) slots across buckets."""
        return sum(b.num_entities * b.rows_per_entity for b in self.buckets)


def build_index_map_design(
    data: GameData,
    random_effect: str,
    shard: str,
    num_entities: int,
    num_buckets: int = 4,
    active_cap: Optional[int] = None,
    entity_multiple: int = 1,
    seed: int = 0,
    dtype=jnp.float32,
    feature_ratio: Optional[float] = None,
    min_support: int = 0,
) -> IndexMapDesign:
    """The bucketed design of a random effect over a padded-ELL shard
    (``ops.sparse.SparseFeatures``), in compact column space: the same
    entities, buckets, reservoir sample and weights as
    :func:`build_bucketed_random_effect_design` gives a dense shard
    (``_bucket_plan``), each lane's columns the union of the columns its
    ACTIVE rows store (``RandomEffectCoordinateInProjectedSpace.scala:26-120``;
    a column only passive rows touch has no data gradient and stays 0
    under L2). A bucket's width is its lanes' largest union rounded up to
    a whole number of ``COMPACT_BLOCK`` columns.

    ``min_support`` drops a lane's column stored in fewer of its active
    rows, ``feature_ratio`` keeps a lane's top ceil(ratio * rows) columns
    by |Pearson correlation| with the label, as the dense builders'
    filters (``filter_features_by_support``, ``select_features_by_pearson``)
    zero them; a dropped column leaves the union.

    Host work O(nnz log nnz), once a run; nothing of size (n, k), (E_b,
    R_b, k_b) or (E, d) is built. One ``game.design`` span, inside it one
    ``game.index_map`` span for the unions, widths and compact ids."""
    from photon_ml_tpu.ops import sparse as sparse_ops

    sf = data.features[shard]
    if not sparse_ops.is_sparse(sf):
        raise ValueError(
            f"random effect {random_effect!r}: an INDEX_MAP design over a "
            f"sparse shard needs a SparseFeatures shard; {shard!r} is not"
        )
    with obs.span(
        "game.design", cat="data", random_effect=random_effect
    ) as sp:
        plan = _bucket_plan(
            np.asarray(data.entity_ids[random_effect]), num_entities,
            num_buckets=num_buckets, active_cap=active_cap,
            entity_multiple=entity_multiple, seed=seed,
        )
        with obs.span(
            "game.index_map", cat="data", random_effect=random_effect
        ) as isp:
            design, attrs = _fill_index_map_design(
                data, random_effect, sf, plan, num_entities, dtype,
                feature_ratio=feature_ratio, min_support=min_support,
            )
            isp.set(**attrs)
        sp.set(
            buckets=design.num_buckets,
            active_slots=design.active_slots,
            bucket_caps=[b.rows_per_entity for b in design.buckets],
            **plan.counted,
        )
    obs.registry().inc(
        "game.re.capped_entities", plan.counted["capped_entities"])
    obs.registry().inc("game.re.passive_rows", plan.counted["passive_rows"])
    return design


def _pearson_keep(pair_lane, pair_inv, x, y, lane_rows, lane_y, lane_yy,
                  ratio, present_pairs):
    """``select_features_by_pearson`` over (lane, column) pairs: the
    per-pair moments of the entries ``x`` (each entry's pair ``pair_inv``,
    its row's label ``y``), the lanes' active row counts and label moments;
    a lane keeps its ceil(ratio * rows) best pairs by |correlation|
    (columns ascending on ties, an intercept-like constant column first),
    among ``present_pairs``."""
    m = pair_lane.size
    s1 = np.bincount(pair_inv, weights=x, minlength=m)
    s2 = np.bincount(pair_inv, weights=x * x, minlength=m)
    sxy = np.bincount(pair_inv, weights=x * y, minlength=m)
    s1, s2, sxy = (np.where(present_pairs, a, 0.0) for a in (s1, s2, sxy))
    n = lane_rows[pair_lane].astype(np.float64)
    ly, lyy = lane_y[pair_lane], lane_yy[pair_lane]
    numerator = n * sxy - s1 * ly
    feat_var = np.abs(n * s2 - s1 * s1)
    label_var = np.maximum(n * lyy - ly * ly, 0.0)
    denominator = np.sqrt(feat_var) * np.sqrt(label_var)
    label_const = label_var < 1e-9 * np.maximum(n * lyy, 1.0)
    score = np.where(label_const, 0.0, numerator / (denominator + 1e-12))
    present = s2 > 0.0
    constant = present & (feat_var < 1e-9 * np.maximum(n * s2, 1.0))
    # pairs run lane by lane, columns ascending: the first constant pair
    # of each lane is its intercept
    first = np.r_[True, pair_lane[1:] != pair_lane[:-1]]
    run = np.cumsum(constant)
    before = np.maximum.accumulate(np.where(first, run - constant, 0))
    first_const = constant & (run - before == 1)
    score = np.where(constant, 0.0, score)
    score = np.where(first_const, 1.0, score)
    score = np.where(present, np.abs(score), -np.inf)
    order = np.lexsort((np.arange(m), -score, pair_lane))
    lane_start = np.searchsorted(pair_lane[order], pair_lane[order], "left")
    rank = np.empty(m, np.int64)
    rank[order] = np.arange(m) - lane_start
    keep_count = np.ceil(ratio * lane_rows).astype(np.int64)
    return rank < keep_count[pair_lane]


def _fill_index_map_design(data, random_effect, sf, plan, num_entities,
                           dtype, *, feature_ratio, min_support):
    """(:class:`IndexMapDesign`, the ``game.index_map`` span's attributes)
    of :func:`build_index_map_design`, for the rows ``plan`` places."""
    from photon_ml_tpu.game.projectors import RaggedIndexMap

    if feature_ratio is not None and feature_ratio <= 0:
        raise ValueError(
            f"feature ratio must be positive, got {feature_ratio}")
    ind = np.asarray(sf.indices)
    val = np.asarray(sf.values).astype(np.dtype(jnp.dtype(dtype)))
    d, width = sf.d, ind.shape[1]
    lanes = [int(e.size) for e in plan.entity_index]
    total = int(sum(lanes))
    lane_base = np.concatenate([[0], np.cumsum(lanes)])[:-1]
    # every stored entry of the active rows, by global lane (the buckets'
    # lanes end to end)
    glane = lane_base[plan.buckets] + plan.lanes
    active_ind = ind[plan.rows]
    stored = active_ind < d
    entry_row, entry_slot = np.nonzero(stored)
    entry_lane = glane[entry_row]
    entry_col = active_ind[stored].astype(np.int64)
    entry_val = val[plan.rows][stored]
    pairs, pair_inv = np.unique(
        entry_lane.astype(np.int64) * d + entry_col, return_inverse=True)
    pair_lane = pairs // d
    keep = np.ones(pairs.size, bool)
    if min_support > 0:
        support = np.bincount(pair_inv, weights=entry_val != 0,
                              minlength=pairs.size)
        keep &= support >= min_support
    if feature_ratio is not None:
        row_label = np.asarray(data.labels, np.float64)[plan.rows]
        lane_rows = np.bincount(glane, minlength=total)
        lane_y = np.bincount(glane, weights=row_label, minlength=total)
        lane_yy = np.bincount(glane, weights=row_label ** 2, minlength=total)
        keep &= _pearson_keep(
            pair_lane, pair_inv, entry_val.astype(np.float64),
            row_label[entry_row], lane_rows, lane_y, lane_yy,
            feature_ratio, keep)
    # the kept pairs, lane by lane and columns ascending: a pair's local
    # id is its rank inside its lane
    kept = np.flatnonzero(keep)
    kept_lane = pair_lane[kept]
    union = np.bincount(kept_lane, minlength=total)
    first_of_lane = np.concatenate([[0], np.cumsum(union)])[:-1]
    local = np.arange(kept.size) - first_of_lane[kept_lane]
    index_map = RaggedIndexMap.from_unions(
        union=union, lanes=lanes, entity_index=plan.entity_index,
        pair_lane=kept_lane, pair_column=pairs[kept] % d,
        pair_local=local, num_entities=num_entities, original_dim=d,
    )
    entry_local = np.full(pairs.size, -1, np.int64)
    entry_local[kept] = local
    entry_local = entry_local[pair_inv]
    entry_kept = entry_local >= 0

    buckets, stored_by_bucket, rows_by_bucket = [], [], []
    for b, (cap_b, idx) in enumerate(zip(plan.caps, plan.entity_index)):
        shape = (idx.size, cap_b)
        labels = np.zeros(shape, np.float64)
        weights = np.zeros(shape, np.float64)
        mask = np.zeros(shape, np.float64)
        row_index = np.full(shape, -1, np.int64)
        sel = plan.buckets == b
        at = (plan.lanes[sel], plan.slots[sel])
        rows = plan.rows[sel]
        labels[at] = data.labels[rows]
        weights[at] = data.weights[rows] * plan.rescale[sel]
        mask[at] = 1.0
        row_index[at] = rows
        columns = np.zeros((idx.size, width, cap_b), np.int32)
        values = np.zeros((idx.size, width, cap_b), val.dtype)
        mine = entry_kept & sel[entry_row]
        r = entry_row[mine]
        spot = (plan.lanes[r], entry_slot[mine], plan.slots[r])
        columns[spot] = entry_local[mine]
        values[spot] = entry_val[mine]
        stored_by_bucket.append(int(np.count_nonzero(mine)))
        rows_by_bucket.append(int(rows.size))
        buckets.append(CompactEllBucket(
            columns=jnp.asarray(columns),
            values=jnp.asarray(values, dtype),
            labels=jnp.asarray(labels, dtype),
            weights=jnp.asarray(weights, dtype),
            mask=jnp.asarray(mask, dtype),
            row_index=jnp.asarray(row_index, jnp.int32),
        ))

    # every row's entries, active and passive, as flat table positions
    row_slots, row_values = index_map.row_positions(
        np.asarray(data.entity_ids[random_effect]), ind, val)
    design = IndexMapDesign(
        buckets=buckets,
        entity_index=plan.entity_index,
        index_map=index_map,
        row_slots=jnp.asarray(row_slots.T),
        row_values=jnp.asarray(row_values.T, dtype),
        num_entities=num_entities,
    )
    padded_slots = sum(
        int(np.prod(b.columns.shape)) for b in buckets)
    return design, dict(
        entities=plan.counted["entities"],
        union_columns=int(kept.size),
        padded_columns=index_map.size,
        widths=list(index_map.widths),
        lanes=list(index_map.lanes),
        stored_slots=int(np.count_nonzero(entry_kept)),
        padded_slots=padded_slots,
        stored_by_bucket=stored_by_bucket,
        rows_by_bucket=rows_by_bucket,
        row_slots_stored=int(np.count_nonzero(row_values)),
    )


# ---------------------------------------------------------------------------
# entity-sharded layout (docs/PARALLEL.md): shard_map'd GAME descent
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EntityShardAssignment:
    """Entity -> mesh-shard ownership for entity-sharded GAME descent.

    Ownership uses the SAME round-robin rule as the sharded checkpoint
    writer (``io.checkpoint.shard_rows``: shard p owns rows ``p::P`` of
    the global entity order), so the device layout and the checkpoint
    shard layout derive from one rule and compose entity-keyed: a
    restore at ANY width re-keys rows by entity
    (``reindex_entity_params``), pad rows re-initialize to zero.

    The device table stores entities SHARD-MAJOR (shard p's entities
    contiguous, each shard padded to ``rows_per_shard``) so a plain
    NamedSharding block split puts each shard's rows on its device.

    stored_to_global: (padded_rows,) int64 stored row -> global entity
                      (``num_entities`` = pad sentinel).
    global_to_stored: (num_entities + 1,) int64 inverse; the last slot
                      maps the global sentinel to the stored sentinel
                      ``padded_rows``.
    """

    num_entities: int
    num_shards: int
    rows_per_shard: int
    stored_to_global: np.ndarray
    global_to_stored: np.ndarray

    @property
    def padded_rows(self) -> int:
        return self.num_shards * self.rows_per_shard

    def shard_of_stored(self, stored: np.ndarray) -> np.ndarray:
        return np.minimum(
            np.asarray(stored, np.int64) // self.rows_per_shard,
            self.num_shards - 1,
        )

    def owner_of_global(self, entities: np.ndarray) -> np.ndarray:
        """Owning shard of each GLOBAL entity index — THE ownership
        lookup shared by entity-sharded training, sharded checkpoints,
        and shard-routed serving (all derive from ``shard_rows``).
        Callers pass valid indices in [0, num_entities)."""
        ents = np.asarray(entities, np.int64)
        return self.shard_of_stored(self.global_to_stored[ents])

    def local_of_global(self, entities: np.ndarray) -> np.ndarray:
        """Row of each GLOBAL entity index within its owner shard's
        block (the shard-LOCAL gather index a per-shard table slice
        uses)."""
        ents = np.asarray(entities, np.int64)
        stored = self.global_to_stored[ents]
        return (stored - self.shard_of_stored(stored) * self.rows_per_shard)

    def stored_entity_keys(self, global_keys) -> list:
        """Global entity-key list -> the STORED (shard-major) order the
        device table holds, pad rows keyed uniquely so checkpoint
        re-keying never aliases them onto real entities."""
        keys = list(global_keys)
        if len(keys) != self.num_entities:
            raise ValueError(
                f"{len(keys)} entity keys for {self.num_entities} entities"
            )
        return [
            (
                str(keys[g])
                if g < self.num_entities
                else f"__entity_pad__:{i}"
            )
            for i, g in enumerate(self.stored_to_global)
        ]

    def table_to_global(self, stored_table: np.ndarray) -> np.ndarray:
        """Stored (shard-major, padded) table -> global entity order."""
        stored_table = np.asarray(stored_table)
        return stored_table[self.global_to_stored[: self.num_entities]]

    def table_from_global(self, global_table: np.ndarray) -> np.ndarray:
        """Global entity order -> stored (shard-major, padded) layout;
        pad rows zero."""
        global_table = np.asarray(global_table)
        out = np.zeros(
            (self.padded_rows,) + global_table.shape[1:],
            global_table.dtype,
        )
        real = self.stored_to_global < self.num_entities
        out[real] = global_table[self.stored_to_global[real]]
        return out


def entity_shard_assignment(
    num_entities: int, num_shards: int
) -> EntityShardAssignment:
    """Build the round-robin entity -> shard assignment (shared rule:
    ``io.checkpoint.shard_rows``)."""
    from photon_ml_tpu.io.checkpoint import shard_rows

    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    per_shard = -(-num_entities // num_shards) if num_entities else 1
    padded = per_shard * num_shards
    stored_to_global = np.full(padded, num_entities, np.int64)
    for p in range(num_shards):
        rows = np.asarray(
            list(shard_rows(num_entities, p, num_shards)), np.int64
        )
        stored_to_global[
            p * per_shard : p * per_shard + rows.size
        ] = rows
    global_to_stored = np.full(num_entities + 1, padded, np.int64)
    real = stored_to_global < num_entities
    global_to_stored[stored_to_global[real]] = np.flatnonzero(real)
    return EntityShardAssignment(
        num_entities=num_entities,
        num_shards=num_shards,
        rows_per_shard=per_shard,
        stored_to_global=stored_to_global,
        global_to_stored=global_to_stored,
    )


@dataclasses.dataclass(frozen=True)
class RowExchangePlan:
    """Static plan of the on-device row exchange between the CANONICAL
    row partition (the first random effect's: labels, base offsets,
    weights and every coordinate's scores live in it) and the partition
    of another random effect, whose rows sit on other shards in another
    order. Built once on the host from the two ``row_perm``s.

    Shard p packs, for every destination q, the rows it holds that q
    holds in the other order into a block of ``block_rows`` slots (the
    largest (source, destination) count; the rest of a block is pad),
    one ``all_to_all`` moves block (p, q) to shard q, and q reads every
    row of its own order out of what it received by ONE gather through a
    static inverse map (a gather costs a tenth of a scatter a row on the
    chip: PERF.md section 6, PR 33). The way back runs the same blocks
    in the other direction. Every index array is flat over the shards,
    shard p's segment first, so a block split over the 'entity' mesh
    axis hands each shard its own; -1 marks a pad (value 0).

    send_to_owner:     (S * S * B,) canonical-local row of slot (q, j)
    recv_at_owner:     (S * R_own,) slot p * B + j of what arrived, for
                       each row of the owner's order
    send_to_canonical: (S * S * B,) owner-local row of slot (p, j)
    recv_at_canonical: (S * R_can,) slot q * B + j, for each canonical row
    """

    num_shards: int
    block_rows: int
    canonical_rows_per_shard: int
    own_rows_per_shard: int
    real_rows: int
    send_to_owner: np.ndarray
    recv_at_owner: np.ndarray
    send_to_canonical: np.ndarray
    recv_at_canonical: np.ndarray

    @property
    def canonical_padded_rows(self) -> int:
        return self.num_shards * self.canonical_rows_per_shard

    @property
    def exchanged_rows(self) -> int:
        """Slots one exchange moves, pads included, over all shards."""
        return self.num_shards * self.num_shards * self.block_rows


@dataclasses.dataclass(frozen=True)
class EntityRowPartition:
    """Row-space permutation grouping batch rows by their entity's owner
    shard (entity-PARTITIONED rows — the device analog of the
    reference's ``RandomEffectIdPartitioner`` placement): shard p's rows
    sit in the contiguous block ``[p*R, (p+1)*R)``, padded with -1
    sentinel rows so every shard holds the same count. Applying the
    permutation ONCE at setup keeps every per-row array of the
    coordinate (features, entity lanes) aligned with the 'entity' mesh
    axis — its update then solves and scores without crossing shards.

    row_perm: (padded_rows,) int64 permuted position -> original row
              (-1 = pad).
    exchange: the :class:`RowExchangePlan` to and from the canonical
              partition; None where this partition IS the canonical one
              (its coordinate exchanges nothing).
    """

    num_shards: int
    rows_per_shard: int
    row_perm: np.ndarray
    exchange: Optional[RowExchangePlan] = None

    @property
    def padded_rows(self) -> int:
        return self.num_shards * self.rows_per_shard

    def apply(self, column: np.ndarray, fill=0.0) -> np.ndarray:
        """Permute one per-row array into the sharded order (pad rows
        carry ``fill``)."""
        column = np.asarray(column)
        # one gather of whole rows, then the few pad rows overwritten: a
        # masked assignment costs several times as much at 10^7 rows
        out = np.take(column, np.maximum(self.row_perm, 0), axis=0)
        out[self.row_perm < 0] = fill
        return out

    def restore(self, column: np.ndarray) -> np.ndarray:
        """Inverse of :meth:`apply` (drops pad rows)."""
        column = np.asarray(column)
        n = int((self.row_perm >= 0).sum())
        out = np.zeros((n,) + column.shape[1:], column.dtype)
        real = self.row_perm >= 0
        out[self.row_perm[real]] = column[real]
        return out


def row_exchange_plan(
    canonical: EntityRowPartition, own: EntityRowPartition
) -> Optional[RowExchangePlan]:
    """The exchange plan between ``canonical`` and ``own``, two partitions
    of the same rows; None where they are the same partition."""
    if canonical.num_shards != own.num_shards:
        raise ValueError(
            f"partitions of {canonical.num_shards} and {own.num_shards} "
            "shards cannot exchange rows"
        )
    if np.array_equal(canonical.row_perm, own.row_perm):
        return None
    shards = canonical.num_shards
    r_can, r_own = canonical.rows_per_shard, own.rows_per_shard
    at_can = np.flatnonzero(canonical.row_perm >= 0)
    at_own = np.flatnonzero(own.row_perm >= 0)
    n = at_can.size
    if at_own.size != n:
        raise ValueError(
            f"partitions hold {n} and {at_own.size} rows; an exchange "
            "needs the same rows in both"
        )
    own_of_row = np.empty(n, np.int64)
    own_of_row[own.row_perm[at_own]] = at_own
    # in canonical order: the row's place in both partitions
    own_pos = own_of_row[canonical.row_perm[at_can]]
    src, dst = at_can // r_can, own_pos // r_own
    pair = src * shards + dst
    counts = np.bincount(pair, minlength=shards * shards)
    block = max(int(counts.max(initial=0)), 1)
    order = np.argsort(pair, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.empty(n, np.int64)
    slot[order] = np.arange(n) - starts[pair[order]]
    per_shard = shards * block
    send_to_owner = np.full(shards * per_shard, -1, np.int32)
    send_to_owner[src * per_shard + dst * block + slot] = at_can - src * r_can
    recv_at_owner = np.full(own.padded_rows, -1, np.int32)
    recv_at_owner[own_pos] = src * block + slot
    send_to_canonical = np.full(shards * per_shard, -1, np.int32)
    send_to_canonical[dst * per_shard + src * block + slot] = (
        own_pos - dst * r_own
    )
    recv_at_canonical = np.full(canonical.padded_rows, -1, np.int32)
    recv_at_canonical[at_can] = dst * block + slot
    return RowExchangePlan(
        num_shards=shards,
        block_rows=block,
        canonical_rows_per_shard=r_can,
        own_rows_per_shard=r_own,
        real_rows=int(n),
        send_to_owner=send_to_owner,
        recv_at_owner=recv_at_owner,
        send_to_canonical=send_to_canonical,
        recv_at_canonical=recv_at_canonical,
    )


def entity_partition_game_data(
    data: GameData,
    random_effect: str,
    assignment: EntityShardAssignment,
    canonical: Optional[EntityRowPartition] = None,
    feature_shards=None,
):
    """Permute a :class:`GameData` into the entity-partitioned row order
    of ``random_effect`` (rows grouped by their entity's owner shard,
    pad rows masked by zero weight): the ONE-time layout step of
    entity-sharded GAME descent, once a random effect. Returns
    ``(permuted GameData, EntityRowPartition)``. Dense and padded-ELL
    feature shards both permute (``feature_shards`` names the ones to
    carry; all by default); every random effect's id column rides along
    row-aligned.

    Rows cannot be grouped by two entity types at once, so every
    sharded random effect gets a partition of its own. The partition of
    the FIRST one is the canonical row order: labels, base offsets,
    weights, the fixed effect's batch and every coordinate's scores live
    in it. A later random effect passes that partition as ``canonical``
    and gets its own partition back with the static
    :class:`RowExchangePlan` between the two attached
    (``partition.exchange``; None where the two orders coincide): its
    coordinate holds design, row features and entity lanes in ITS order
    and moves residual offsets in and scores out through the plan, on
    the device.

    The whole step is one ``partition.entity_layout`` span carrying the
    layout's counts: ``rows``, ``rows_per_shard``, ``padded_rows`` and,
    with a plan, ``exchange_block_rows`` / ``exchange_real_rows``."""
    from photon_ml_tpu.ops.sparse import SparseFeatures, is_sparse, is_structured

    with obs.span(
        "partition.entity_layout", cat="partition",
        random_effect=random_effect, shards=assignment.num_shards,
        entities=assignment.num_entities,
    ) as sp:
        part = entity_partition_rows(
            data.entity_ids[random_effect], assignment
        )
        counted = dict(
            rows=int(np.shape(data.labels)[0]),
            rows_per_shard=part.rows_per_shard,
            padded_rows=part.padded_rows,
        )
        if canonical is not None:
            plan = row_exchange_plan(canonical, part)
            part = dataclasses.replace(part, exchange=plan)
            if plan is not None:
                counted.update(
                    exchange_block_rows=plan.block_rows,
                    exchange_real_rows=plan.real_rows,
                )

        def permute_features(v):
            if is_sparse(v):
                return SparseFeatures(
                    indices=part.apply(v.indices, fill=v.d),
                    values=part.apply(v.values),
                    d=v.d,
                )
            if is_structured(v):
                raise ValueError(
                    "entity partitioning permutes dense or plain-ELL "
                    f"shards; got {type(v).__name__}"
                )
            return part.apply(v)

        permuted = GameData(
            features={
                k: permute_features(v)
                for k, v in data.features.items()
                if feature_shards is None or k in feature_shards
            },
            labels=part.apply(data.labels),
            offsets=part.apply(data.offsets),
            weights=part.apply(data.weights),  # pad rows weight 0: masked
            entity_ids={
                k: part.apply(v, fill=-1)
                for k, v in data.entity_ids.items()
            },
        )
        sp.set(**counted)
    return permuted, part


class EntityShardLayout(NamedTuple):
    """One sharded random effect's layout: the rows in its own order,
    its entity -> shard assignment and its row partition (with the
    exchange plan, for every random effect but the first)."""

    data: GameData
    assignment: EntityShardAssignment
    partition: EntityRowPartition

    def bucketed_design(
        self, random_effect: str, shard: str, **design_options
    ) -> BucketedRandomEffectDesign:
        """:func:`build_bucketed_random_effect_design` over this
        layout's rows, laid out on the HOST's device where the process
        has one. The sharded coordinate regroups every bucket's lanes by
        owner shard with numpy and puts each shard's block straight on
        its chip: a design on the default device would sit whole on the
        first chip only to be fetched back."""
        try:
            on_host = jax.default_device(jax.local_devices(backend="cpu")[0])
        except RuntimeError:  # the process was given no host backend
            on_host = contextlib.nullcontext()
        with on_host:
            return build_bucketed_random_effect_design(
                self.data,
                random_effect,
                shard,
                self.assignment.num_entities,
                **design_options,
            )


def entity_shard_layouts(
    data: GameData,
    num_entities: Mapping[str, int],
    num_shards: int,
    feature_shards: Mapping[str, Collection[str]],
) -> Dict[str, EntityShardLayout]:
    """THE layout step of entity-sharded descent over any number of
    random effects: one :func:`entity_partition_game_data` a random
    effect of ``num_entities`` (random effect -> table rows), in its
    order. The first one's partition is the canonical row order; its
    ``data`` carries every feature shard (the fixed-effect batches and
    whatever scores the whole model come from it). Every later one
    carries the shards its coordinates read (``feature_shards``: random
    effect -> shards) and the exchange plan."""
    layouts: Dict[str, EntityShardLayout] = {}
    canonical = None
    for re_key, entities in num_entities.items():
        assignment = entity_shard_assignment(entities, num_shards)
        own, partition = entity_partition_game_data(
            data,
            re_key,
            assignment,
            canonical=canonical,
            feature_shards=(
                None if canonical is None else feature_shards[re_key]
            ),
        )
        canonical = canonical or partition
        layouts[re_key] = EntityShardLayout(own, assignment, partition)
    return layouts


def entity_partition_rows(
    entity_ids: np.ndarray, assignment: EntityShardAssignment
) -> EntityRowPartition:
    """Group rows by their entity's owner shard (stable within a
    shard). Rows with unknown entities (-1) spread round-robin — they
    participate in no random-effect solve, so any shard balances."""
    eids = np.asarray(entity_ids, np.int64)
    n = eids.shape[0]
    known = eids >= 0
    owner = np.empty(n, np.int64)
    owner[known] = assignment.shard_of_stored(
        assignment.global_to_stored[eids[known]]
    )
    owner[~known] = np.arange(int((~known).sum())) % assignment.num_shards
    counts = np.bincount(owner, minlength=assignment.num_shards)
    per = int(counts.max()) if counts.size else 1
    row_perm = np.full(per * assignment.num_shards, -1, np.int64)
    order = np.argsort(owner, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    slot = np.arange(n) - starts[owner[order]]
    row_perm[owner[order] * per + slot] = order
    return EntityRowPartition(
        num_shards=assignment.num_shards,
        rows_per_shard=per,
        row_perm=row_perm,
    )


def build_entity_vocabulary(raw_ids: np.ndarray):
    """Map raw entity keys -> dense [0, E) indices (the analog of the
    reference's per-entity partitioner + index maps). Returns (vocab dict,
    (n,) int32 index column)."""
    uniq = np.unique(raw_ids)
    vocab = {k: i for i, k in enumerate(uniq.tolist())}
    idx = np.asarray([vocab[k] for k in raw_ids.tolist()], np.int32)
    return vocab, idx


def apply_entity_vocabulary(vocab: dict, raw_ids: np.ndarray) -> np.ndarray:
    """Index new data against an existing vocabulary; unknown -> -1
    (scores 0, ``model/RandomEffectModel.scala:117-146``)."""
    return np.asarray(
        [vocab.get(k, -1) for k in raw_ids.tolist()], np.int32
    )
