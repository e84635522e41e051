"""Device-resident online GAME scoring engine.

The offline driver (``cli/score.py``) is a batch job: load model, score one
big dataset, exit. The ROADMAP's north star — "serve heavy traffic from
millions of users as fast as the hardware allows" — needs the opposite
shape: a *resident* engine that loads the GAME model once, keeps it pinned
on device, and answers small concurrent requests at low latency. Three
design rules make that work:

1. **Device residency.** The fixed-effect vector, every random-effect
   table (pre-compacted through :class:`~photon_ml_tpu.game.scoring.
   CompactReTable` — (E, k) active pairs instead of a dense (E, d) slab),
   and factored latent tables are transferred once at construction and
   passed to every call as device arrays; requests move only O(batch)
   bytes host->device.

2. **Power-of-two padded buckets.** XLA specializes each compiled
   executable to static shapes, so naively scoring a 7-row batch then an
   8-row batch recompiles. Every batch is padded to the next power of two
   (floor ``min_bucket``), and the engine AOT-compiles one executable per
   bucket (``jax.jit(...).lower(...).compile()``); after warmup on a fixed
   bucket set, steady-state traffic NEVER recompiles — asserted in tests
   against both the engine's own compile counter and the process-wide
   ``jax.monitoring`` compile-event stream (:mod:`.stats`).

3. **Cold-start = fixed-effect-only.** A request whose entity id is
   unknown (or absent) carries index -1, and every random-effect kernel
   scores it 0 — the reference's cogroup-with-default-0 semantics
   (``model/RandomEffectModel.scala:117-146``), bit-identical to
   ``score_game_data`` on the same rows.

The engine is synchronous and thread-safe for scoring; coalescing of
concurrent requests belongs to :mod:`.batcher`, versioning/hot-reload to
:mod:`.registry`.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import obs
from photon_ml_tpu.resilience import faults as _faults

from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.game.scoring import (
    CompactReTable,
    _factored_scores,
    _fixed_scores,
    _random_scores_compact_dense,
    precompact_model,
)
from photon_ml_tpu.io.schemas import NAME_TERM_DELIMITER
from photon_ml_tpu.serving.stats import ServingStats, install_compile_listener

DEFAULT_MIN_BUCKET = 8
DEFAULT_MAX_BUCKET = 1024


def bucket_size(n: int, min_bucket: int = DEFAULT_MIN_BUCKET) -> int:
    """Smallest power of two >= max(n, min_bucket) — the shared padded-batch
    policy of the online engine AND the offline driver (``cli/score.py``),
    so both hit the same compiled executables."""
    if n <= 0:
        raise ValueError(f"batch must be non-empty, got {n} rows")
    return 1 << (max(n, min_bucket) - 1).bit_length()


def warmup_buckets(
    max_batch: int, min_bucket: int = DEFAULT_MIN_BUCKET
) -> Sequence[int]:
    """The power-of-two ladder [bucket_size(min_bucket) .. bucket_size(
    max_batch)] — the fixed bucket set to precompile so any batch of at
    most ``max_batch`` rows dispatches without compiling."""
    out = []
    b = bucket_size(1, min_bucket)
    top = bucket_size(max_batch, min_bucket)
    while b <= top:
        out.append(b)
        b *= 2
    return out


def _pad_rows(x: np.ndarray, rows: int, fill=0) -> np.ndarray:
    if x.shape[0] == rows:
        return x
    pad = np.full((rows - x.shape[0],) + x.shape[1:], fill, x.dtype)
    return np.concatenate([x, pad], axis=0)


def pad_game_data(data: GameData, rows: int) -> GameData:
    """Pad every row-aligned column of a :class:`GameData` to ``rows``:
    features with zero rows (ELL shards with all-pad rows), entity ids
    with -1 (scores 0), labels/offsets/weights with 0. Padding is
    algebraically invisible to scoring; callers slice scores back to the
    real row count. Used by ``cli/score.py`` so ragged final batches land
    on the same power-of-two executables as everything else."""
    from photon_ml_tpu.ops.sparse import SparseFeatures, is_sparse, is_structured

    n = data.num_rows
    if rows == n:
        return data
    if rows < n:
        raise ValueError(f"cannot pad {n} rows down to {rows}")
    features = {}
    for name, v in data.features.items():
        if is_sparse(v):
            extra = rows - v.indices.shape[0]
            pad_i = jnp.full((extra, v.nnz_per_row), v.d, v.indices.dtype)
            pad_v = jnp.zeros((extra, v.nnz_per_row), v.values.dtype)
            features[name] = SparseFeatures(
                indices=jnp.concatenate([v.indices, pad_i], axis=0),
                values=jnp.concatenate([v.values, pad_v], axis=0),
                d=v.d,
            )
        elif is_structured(v):
            raise ValueError(
                f"shard {name!r}: only dense and plain-ELL shards pad "
                "(GameData already rejects hybrid containers)"
            )
        else:
            features[name] = _pad_rows(np.asarray(v), rows)
    return GameData(
        features=features,
        labels=_pad_rows(data.labels, rows),
        offsets=_pad_rows(data.offsets, rows),
        weights=_pad_rows(data.weights, rows),
        entity_ids={
            k: _pad_rows(v, rows, fill=-1)
            for k, v in data.entity_ids.items()
        },
    )


class SharedCompileCache:
    """Process-wide AOT bucket-executable ladder shared across engines.

    Compiled bucket executables take the model params as ARGUMENTS, so
    the program depends only on the engine's structural signature —
    class, coordinate order, shard map, RE keys, param shapes/dtypes,
    placement, and the per-call (bucket, dims, fixed_only) contract —
    never on the weights. N tenants serving same-shaped models (the
    photon-ml fleet norm: one architecture, per-market weights) share
    ONE compile per bucket instead of paying N (docs/FRONTEND.md).

    Thread-safe with build-once semantics: a per-key lock means two
    tenants warming the same bucket concurrently compile once and both
    get the survivor, without serializing compiles for DIFFERENT keys
    behind one global lock.
    """

    def __init__(self):
        self._cache: Dict[tuple, object] = {}
        self._locks: Dict[tuple, threading.Lock] = {}
        self._meta = threading.Lock()
        self.hits = 0
        self.compiles = 0

    def get(self, key: tuple, build: Callable[[], object]) -> object:
        with self._meta:
            hit = self._cache.get(key)
            if hit is not None:
                self.hits += 1
                return hit
            lock = self._locks.setdefault(key, threading.Lock())
        with lock:
            with self._meta:
                hit = self._cache.get(key)
                if hit is not None:
                    self.hits += 1
                    return hit
            built = build()
            with self._meta:
                self._cache[key] = built
                self.compiles += 1
            return built

    def snapshot(self) -> dict:
        with self._meta:
            return {
                "entries": len(self._cache),
                "hits": int(self.hits),
                "compiles": int(self.compiles),
            }


@dataclasses.dataclass
class ScoreRequest:
    """One scoring request.

    features: feature -> value; keys are ``"name\\x01term"`` strings,
        ``(name, term)`` tuples, or bare names (empty term). Applied
        against every shard's vocabulary — each shard picks the features
        it knows, exactly like ingest; unknown keys are ignored.
    entities: random-effect type -> raw entity id (missing or unknown ids
        score fixed-effect-only).
    offset: added to the returned score (the data offset column).
    """

    features: Mapping
    entities: Mapping = dataclasses.field(default_factory=dict)
    offset: float = 0.0


@dataclasses.dataclass
class _ScorePlan:
    """One batch, prepared on the host (``ScoringEngine._prepare``):
    everything ``_score`` needs to dispatch, fetch and finish it."""

    rows: int
    bucket: int
    action: object  # what the serving.score fault site said
    attrs: dict  # further attributes of the serving.score span
    call: object  # () -> scores on the device (the compiled call)
    finish: object  # fetched array -> (rows,) scores
    record: object  # seconds of dispatch + fetch -> stats


class ScoringEngine:
    """In-process online scorer for one loaded GAME model version.

    Construct from in-memory params (``ScoringEngine(params, shards,
    random_effects, shard_vocabs, re_vocabs)``) or straight from a model
    export directory (:meth:`from_model_dir`). Scoring entry points:

    - :meth:`score` — featurize :class:`ScoreRequest` objects and score.
    - :meth:`score_arrays` — pre-featurized (B, d) arrays per shard.
    - :meth:`score_data` — a dense-sharded :class:`GameData` (offline
      parity testing; returns margins WITHOUT offsets, like
      ``score_game_data``).
    """

    def __init__(
        self,
        params: Dict[str, object],
        shards: Dict[str, str],
        random_effects: Dict[str, Optional[str]],
        shard_vocabs: Optional[Dict[str, object]] = None,
        re_vocabs: Optional[Dict[str, dict]] = None,
        *,
        dtype=jnp.float64,
        min_bucket: int = DEFAULT_MIN_BUCKET,
        max_bucket: int = DEFAULT_MAX_BUCKET,
        device=None,
        stats: Optional[ServingStats] = None,
        baseline=None,
        drift=None,
        hbm_cache_entities: Optional[int] = None,
        admission_log_path: Optional[str] = None,
        compile_cache: Optional["SharedCompileCache"] = None,
    ):
        install_compile_listener()
        self.dtype = jnp.empty((), dtype).dtype  # canonicalized (x64 seam)
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self.shards = dict(shards)
        self.random_effects = dict(random_effects)
        self.shard_vocabs = dict(shard_vocabs or {})
        self.re_vocabs = dict(re_vocabs or {})
        self.stats = stats if stats is not None else ServingStats()
        # drift monitor: live request-feature/score sketches vs the
        # model's train-time baseline (obs.quality). Lives ON the engine
        # so a registry hot-reload swaps baseline atomically with the
        # model; gauges/events go to this engine's stats registry.
        if drift is not None:
            self.drift = drift
        elif baseline is not None:
            from photon_ml_tpu.obs.quality import DriftMonitor

            self.drift = DriftMonitor(
                baseline, registry=self.stats.registry
            )
        else:
            self.drift = None
        self._coord_order = sorted(params)
        self._device = device
        self._used_shards = sorted(
            {self.shards[name] for name in self._coord_order}
        )
        # feature dims observable from the raw params (dense tables,
        # fixed vectors, factored projections) — the warmup fallback
        # when a shard has no vocabulary and its params arrive already
        # compacted (compact tables do not carry d)
        self._shard_dim_hints: Dict[str, int] = {}
        for name, p in params.items():
            shard = self.shards[name]
            if hasattr(p, "projection"):
                self._shard_dim_hints[shard] = int(
                    np.shape(p.projection)[0]
                )
            elif isinstance(p, (np.ndarray, jax.Array)) or (
                not hasattr(p, "columns") and np.ndim(p) in (1, 2)
            ):
                dims = np.shape(p)
                self._shard_dim_hints[shard] = int(dims[-1])
        self._re_keys = sorted(
            {rk for rk in self.random_effects.values() if rk is not None}
        )
        # fixed-effect-only coordinates: the degraded-mode scoring set
        # (admission control's "cheaper answer for everyone" fallback)
        self._fixed_coords = [
            name
            for name in self._coord_order
            if self.random_effects.get(name) is None
        ]
        compact = self._precompact(params)
        # repeat-miss admission log (serving/cache.py): the persisted
        # serving->training feedback channel. Both miss streams feed it
        # — tiered-cache misses (known-but-cold entities, noted by the
        # caches below) and unknown entity ids (featurize maps them to
        # -1 and notes the raw key here) — so the retrain orchestrator
        # can admit the repeat-missed tail into the next training set.
        self._admission = None
        if admission_log_path:
            from photon_ml_tpu.serving.cache import AdmissionLog

            self._admission = AdmissionLog(
                admission_log_path, stats=self.stats
            )
        # tiered HBM/host entity cache (serving/cache.py): the hot Zipf
        # head of each entity-keyed table lives in the HBM tier passed to
        # every executable; the cold tail stays in host RAM and promotes
        # asynchronously OFF the scoring path. One cache per RE key so
        # every coordinate sharing that key agrees on slot ids.
        self._caches: Dict[str, object] = {}
        if hbm_cache_entities:
            compact = self._install_caches(compact, int(hbm_cache_entities))
        self._params = self._pin_params(compact)
        jax.block_until_ready(
            [leaf for leaf in jax.tree_util.tree_leaves(self._params)]
        )
        self._make_scorers()
        self._compiled: Dict[object, object] = {}
        self._lock = threading.Lock()
        self.compile_count = 0
        # optional process-wide executable sharing (docs/FRONTEND.md):
        # params are ARGUMENTS of every bucket executable, so engines
        # whose structural signature matches (same class / coordinate
        # order / shard map / param shapes / placement) can run one
        # compiled program with their own weights — N tenants pay one
        # AOT bucket ladder instead of N
        self._shared_cache = compile_cache
        self.shared_compile_hits = 0

    # -- construction hooks (overridden by the entity-sharded engine) ------

    def _precompact(self, params: Dict[str, object]) -> Dict[str, object]:
        """Params -> compact serving form (every (E, d) table becomes a
        :class:`CompactReTable`)."""
        return precompact_model(params)

    def _pin_params(self, compact: Dict[str, object]) -> Dict[str, object]:
        """Pin the compact params device-resident at the serving dtype
        (int32 columns stay int32) and publish the resident-footprint
        gauge. The sharded engine overrides this with the mesh-
        partitioned placement."""

        def put(x):
            a = jnp.asarray(x)
            return (
                jax.device_put(a, self._device)
                if self._device is not None
                else a
            )

        out: Dict[str, object] = {}
        re_bytes = 0
        for name, p in compact.items():
            re_key = self.random_effects.get(name)
            if isinstance(p, CompactReTable):
                out[name] = CompactReTable(
                    columns=put(np.asarray(p.columns, np.int32)),
                    values=put(np.asarray(p.values, self.dtype)),
                )
                re_bytes += (
                    out[name].columns.nbytes + out[name].values.nbytes
                )
            elif hasattr(p, "gamma"):  # FactoredParams
                out[name] = type(p)(
                    gamma=put(np.asarray(p.gamma, self.dtype)),
                    projection=put(np.asarray(p.projection, self.dtype)),
                )
                if re_key is not None:
                    re_bytes += out[name].gamma.nbytes
            else:
                out[name] = put(np.asarray(p, self.dtype))
        # per-process resident entity-table footprint: what ONE process
        # keeps pinned for random effects. The sharded engine's override
        # reports one shard's slice (the ~P x drop the mesh buys); the
        # tiered cache reports its HBM tier, not the host-RAM tail.
        self.stats.registry.set_gauge(
            "serving.shard.resident_re_bytes_per_process", re_bytes
        )
        return out

    def _make_scorers(self) -> None:
        self._scorer = jax.jit(self._score_padded)
        self._scorer_fixed = jax.jit(self._score_padded_fixed)

    def _install_caches(
        self, compact: Dict[str, object], capacity: int
    ) -> Dict[str, object]:
        """Stand up one :class:`~photon_ml_tpu.serving.cache.
        TieredEntityCache` per RE key over every entity-keyed table and
        return params whose entity tables are the HBM-tier arrays."""
        from photon_ml_tpu.serving.cache import TieredEntityCache

        sizes: Dict[str, int] = {}
        for name in self._coord_order:
            re_key = self.random_effects.get(name)
            p = compact[name]
            if re_key is None:
                continue
            rows = int(
                np.shape(p.gamma if hasattr(p, "gamma") else p.columns)[0]
            )
            if sizes.setdefault(re_key, rows) != rows:
                raise ValueError(
                    f"coordinate {name!r}: {rows} entity rows, other "
                    f"coordinates keyed {re_key!r} have {sizes[re_key]}"
                )
        for re_key, rows in sizes.items():
            # admission-log key resolver: global row index -> raw vocab
            # key, so the log speaks entity KEYS (what a training set
            # admits), never positions (the PR-4 bug class)
            reverse = {
                idx: raw
                for raw, idx in (self.re_vocabs.get(re_key) or {}).items()
            }
            self._caches[re_key] = TieredEntityCache(
                re_key,
                num_entities=rows,
                capacity=capacity,
                dtype=self.dtype,
                stats=self.stats,
                admission_log=self._admission,
                entity_key_of=(
                    (lambda e, _r=reverse: str(_r.get(e, e)))
                    if reverse
                    else None
                ),
            )
        out = dict(compact)
        for name in self._coord_order:
            re_key = self.random_effects.get(name)
            if re_key is None:
                continue
            cache = self._caches[re_key]
            p = compact[name]
            if isinstance(p, CompactReTable):
                cache.add_table(
                    name, "columns", np.asarray(p.columns, np.int32)
                )
                cache.add_table(
                    name, "values", np.asarray(p.values, self.dtype)
                )
            elif hasattr(p, "gamma"):
                cache.add_table(
                    name, "gamma", np.asarray(p.gamma, self.dtype)
                )
            else:  # pragma: no cover — precompact leaves only these kinds
                raise ValueError(
                    f"coordinate {name!r}: cannot cache {type(p).__name__}"
                )
        for cache in self._caches.values():
            cache.seal()
        return self._cache_view(out)

    def _cache_view(
        self,
        compact: Dict[str, object],
        tier_tables: Optional[Dict[str, dict]] = None,
    ) -> Dict[str, object]:
        """Params with every cached coordinate's arrays replaced by the
        HBM-tier device arrays (fixed shapes: promotion swaps contents,
        never shapes, so the bucket executables survive). Pass
        ``tier_tables`` (re_key -> tables) to build the view from
        snapshots taken WITH the batch's slot resolution."""
        out = dict(compact)
        for re_key, cache in self._caches.items():
            if tier_tables is not None:
                tiers = tier_tables[re_key]
            else:
                tiers = cache.device_tables()
            for name in self._coord_order:
                if self.random_effects.get(name) != re_key:
                    continue
                p = out[name]
                if isinstance(p, CompactReTable) or (
                    isinstance(p, tuple) and hasattr(p, "columns")
                ):
                    out[name] = CompactReTable(
                        columns=tiers[(name, "columns")],
                        values=tiers[(name, "values")],
                    )
                elif hasattr(p, "gamma"):
                    out[name] = type(p)(
                        gamma=tiers[(name, "gamma")],
                        projection=p.projection,
                    )
        return out

    def _translate_entities(self, entity_ids: Dict[str, np.ndarray]):
        """Global entity indices -> (ids the executables gather with,
        params for THIS call). Without a cache: the identity and the
        pinned params. With one, each RE key's ids map to HBM-tier
        slots — a miss maps to -1 (fixed-effect-only for that row, ==
        cold-start semantics) and enqueues an async promotion; a miss
        costs fidelity on that request, never a stall of the batch.
        Slot resolution and the tier tables are captured under ONE lock
        per cache (and the params view memoized on the generation
        counters), so a promotion racing the batch can never point a
        resolved slot at another entity's rows."""
        if not self._caches:
            return entity_ids, self._params
        out = dict(entity_ids)
        tiers: Dict[str, dict] = {}
        gens = []
        for re_key in sorted(self._caches):
            cache = self._caches[re_key]
            col = entity_ids.get(re_key)
            if col is None:
                gen, tables = cache.tables_snapshot()
            else:
                slots, (gen, tables) = cache.translate(
                    np.asarray(col, np.int32), with_tables=True
                )
                out[re_key] = slots
            tiers[re_key] = tables
            gens.append(gen)
        gens = tuple(gens)
        memo = getattr(self, "_live_memo", None)
        if memo is not None and memo[0] == gens:
            return out, memo[1]
        view = self._cache_view(self._params, tiers)
        self._live_memo = (gens, view)
        return out, view

    def cache_snapshot(self) -> Optional[dict]:
        """Hit/miss/promotion/demotion counters per RE key (None when no
        tiered cache is installed)."""
        if not self._caches:
            return None
        return {rk: c.snapshot() for rk, c in sorted(self._caches.items())}

    def admission_snapshot(self) -> Optional[dict]:
        """Repeat-miss admission-log state (None when no log is
        configured) — surfaced through registry ``health()``."""
        if self._admission is None:
            return None
        return self._admission.snapshot()

    @property
    def admission_log(self):
        return self._admission

    def close(self) -> None:
        """Release background resources (cache promotion workers, the
        admission log's final flush). The registry calls this when a
        version retires; idempotent."""
        for cache in self._caches.values():
            cache.close()
        if self._admission is not None:
            self._admission.flush()

    # -- construction ------------------------------------------------------

    @classmethod
    def from_model_dir(cls, root: str, **kw) -> "ScoringEngine":
        """Load a GAME model export (training-output layout) and stand up
        an engine over it. Integrity verification belongs to the registry
        (:mod:`.registry`) — this loads whatever is on disk. The export's
        quality fingerprint, when present and readable, becomes the
        engine's drift baseline; a missing/corrupt one is counted
        (``quality.baseline_*``) and the engine serves without drift
        monitoring — never refuses to serve."""
        from photon_ml_tpu.io.models import load_game_model_auto
        from photon_ml_tpu.obs.quality import try_load_fingerprint

        params, shards, random_effects, shard_vocabs, re_vocabs = (
            load_game_model_auto(root)
        )
        if "baseline" not in kw and "drift" not in kw:
            kw = dict(kw, baseline=try_load_fingerprint(root))
        return cls(
            params, shards, random_effects, shard_vocabs, re_vocabs, **kw
        )

    # -- traced scoring body ----------------------------------------------

    def _score_padded(self, params, feats, ents):
        """Pure traced body: sum of coordinate scores over padded (B, d)
        dense shards. Shares kernels with ``score_game_data`` so online
        and offline scores agree to float rounding."""
        n = feats[self._used_shards[0]].shape[0]
        with jax.named_scope("score"):
            total = jnp.zeros((n,), self.dtype)
            for name in self._coord_order:
                p = params[name]
                f = feats[self.shards[name]]
                re_key = self.random_effects.get(name)
                if re_key is None:
                    total = total + _fixed_scores(p, f)
                elif hasattr(p, "gamma"):
                    total = total + _factored_scores(
                        p.gamma, p.projection, f, ents[re_key]
                    )
                else:
                    total = total + _random_scores_compact_dense(
                        p.columns, p.values, f, ents[re_key]
                    )
        return total

    def _score_padded_fixed(self, params, feats):
        """Degraded-mode traced body: ONLY the fixed-effect coordinates.
        No entity gathers, no random-effect tables touched — the cheap
        executable admission control falls back to under sustained
        pressure. A model with no fixed coordinate scores 0 (the
        cold-start value every random effect already returns)."""
        n = feats[self._used_shards[0]].shape[0]
        with jax.named_scope("score"):
            total = jnp.zeros((n,), self.dtype)
            for name in self._fixed_coords:
                total = total + _fixed_scores(
                    params[name], feats[self.shards[name]]
                )
        return total

    # -- compilation cache -------------------------------------------------

    def _ensure_compiled(
        self,
        bucket: int,
        dims: Optional[Dict[str, int]] = None,
        fixed_only: bool = False,
    ):
        """Executable for one padded bucket; ``dims`` (shard -> feature
        dim) defaults to the vocabularies' lengths. Shard dims are a fixed
        property of the model, so the cache keys on (bucket, mode)."""
        cache_key = (bucket, "fixed") if fixed_only else bucket
        with self._lock:
            hit = self._compiled.get(cache_key)
        if hit is not None:
            self.stats.record_bucket(bucket, hit=True)
            return hit

        fresh = [False]

        def _build():
            scorer = self._scorer_fixed if fixed_only else self._scorer
            fresh[0] = True
            return scorer.lower(
                self._params,
                *self._abstract_inputs(bucket, dims, fixed_only),
            ).compile()

        if self._shared_cache is not None:
            # local miss: consult the process-wide ladder keyed by the
            # engine's structural signature — a hit means some same-
            # shaped tenant already paid this bucket's compile
            compiled = self._shared_cache.get(
                self._compile_cache_key(bucket, dims, fixed_only), _build
            )
            if not fresh[0]:
                self.shared_compile_hits += 1
        else:
            compiled = _build()
        with self._lock:
            prior = self._compiled.setdefault(cache_key, compiled)
        if prior is compiled and fresh[0]:
            self.compile_count += 1
            self.stats.record_compile()
        self.stats.record_bucket(bucket, hit=False)
        return prior

    def _compile_cache_key(self, bucket, dims, fixed_only) -> tuple:
        """Structural signature under which this engine's executables are
        shareable: everything the traced program depends on EXCEPT the
        weight values. Engines producing equal keys lower byte-identical
        programs, so one tenant's compile serves every tenant."""
        leaves, treedef = jax.tree_util.tree_flatten(self._params)
        return (
            type(self).__name__,
            self._placement_fingerprint(),
            tuple(self._coord_order),
            tuple(sorted(self.shards.items())),
            tuple(sorted(self.random_effects.items())),
            str(self.dtype),
            str(treedef),
            tuple((tuple(l.shape), str(l.dtype)) for l in leaves),
            int(bucket),
            tuple(sorted(dims.items())) if dims else None,
            bool(fixed_only),
        )

    def _placement_fingerprint(self) -> str:
        """Where executables land — part of the shared-cache key because
        a program compiled for one device set cannot run on another. The
        sharded engine overrides with its mesh's device ids."""
        return repr(self._device)

    def _abstract_inputs(self, bucket, dims, fixed_only):
        """Abstract (ShapeDtypeStruct) non-param arguments of one padded
        bucket's executable — the shape contract `_ensure_compiled`
        lowers against. Overridden by the sharded engine (whose routed
        inputs carry a leading shard axis and a fixed-effect mask)."""
        feats_s = {
            s: jax.ShapeDtypeStruct(
                (bucket, dims[s] if dims else self._shard_dim(s)),
                self.dtype,
            )
            for s in self._used_shards
        }
        if fixed_only:
            return (feats_s,)
        ents_s = {
            rk: jax.ShapeDtypeStruct((bucket,), jnp.int32)
            for rk in self._re_keys
        }
        return (feats_s, ents_s)

    def _shard_dim(self, shard: str) -> int:
        """Feature dimension of a shard, from its vocab or its params."""
        if shard in self.shard_vocabs:
            return len(self.shard_vocabs[shard])
        if shard in self._shard_dim_hints:
            return self._shard_dim_hints[shard]
        for name in self._coord_order:
            if self.shards[name] != shard:
                continue
            p = self._params[name]
            if isinstance(p, CompactReTable):
                # compact pad column id == d by construction
                raise ValueError(
                    f"shard {shard!r}: dimension unknown without a "
                    "vocabulary (compact tables do not carry d)"
                )
            if hasattr(p, "gamma"):
                return p.projection.shape[0]
            return int(np.shape(p)[-1])
        raise KeyError(f"no coordinate uses shard {shard!r}")

    def warmup(
        self,
        buckets: Optional[Sequence[int]] = None,
        max_batch: Optional[int] = None,
        include_degraded: bool = False,
    ) -> Sequence[int]:
        """AOT-compile the executables for a fixed bucket set (default:
        the power-of-two ladder up to ``max_batch`` or ``max_bucket``).
        After this, any batch of at most the largest warmed bucket scores
        with zero compiles. ``include_degraded`` also warms the
        fixed-effect-only ladder, so the FIRST degraded batch under
        overload doesn't pay a compile right when latency matters most.
        Returns the warmed buckets."""
        if buckets is None:
            buckets = warmup_buckets(
                max_batch or self.max_bucket, self.min_bucket
            )
        # watermark the warmup: AOT-compiling the bucket ladder is the
        # engine's HBM commitment point (one executable + workspace per
        # bucket) — regressions here show as hbm.serving.warmup.* gauges
        with obs.hbm_watermark("serving.warmup"):
            for b in buckets:
                self._ensure_compiled(int(b))
                if include_degraded:
                    self._ensure_compiled(int(b), fixed_only=True)
        return list(buckets)

    # -- featurization (host-side, numpy only: no tracing on this path) ----

    def _feature_index(self, shard: str, key) -> Optional[int]:
        vocab = self.shard_vocabs[shard]
        if isinstance(key, tuple):
            return vocab.get(*key)
        if NAME_TERM_DELIMITER not in key:
            key = key + NAME_TERM_DELIMITER
        return vocab.key_to_index.get(key)

    def featurize(self, requests: Sequence[ScoreRequest]):
        """Requests -> (dense (B, d) per shard, (B,) int32 per RE type,
        (B,) offsets). Unknown feature keys are ignored (each shard picks
        what its vocabulary knows, like ingest); unknown entity ids map to
        -1 (cold start); shard intercept columns are set to 1.0 exactly as
        ingest injects them."""
        from photon_ml_tpu.io.models import _maybe_int

        if not self.shard_vocabs:
            raise ValueError(
                "featurize needs shard vocabularies; construct the engine "
                "with shard_vocabs or use score_arrays/score_data"
            )
        b = len(requests)
        feats = {
            s: np.zeros((b, len(self.shard_vocabs[s])), self.dtype)
            for s in self._used_shards
        }
        for s in self._used_shards:
            icpt = self.shard_vocabs[s].intercept_index
            if icpt is not None:
                feats[s][:, icpt] = 1.0
        for i, r in enumerate(requests):
            for key, val in r.features.items():
                for s in self._used_shards:
                    j = self._feature_index(s, key)
                    if j is not None:
                        feats[s][i, j] = val
        ents = {
            rk: np.full(b, -1, np.int32) for rk in self._re_keys
        }
        for rk in self._re_keys:
            vocab = self.re_vocabs.get(rk, {})
            col = ents[rk]
            unknown = []
            for i, r in enumerate(requests):
                raw = r.entities.get(rk)
                if raw is None:
                    continue
                e = vocab.get(raw)
                if e is None:
                    e = vocab.get(_maybe_int(raw))
                if e is not None:
                    col[i] = e
                else:
                    unknown.append(str(raw))
            if unknown and self._admission is not None:
                # entities the model has never seen: the other half of
                # the admission stream (cache misses cover the known-
                # but-cold half)
                self._admission.note(rk, unknown)
        offsets = np.asarray([r.offset for r in requests], np.float64)
        return feats, ents, offsets

    # -- scoring -----------------------------------------------------------

    def score_arrays(
        self,
        features: Dict[str, np.ndarray],
        entity_ids: Optional[Dict[str, np.ndarray]] = None,
        offsets: Optional[np.ndarray] = None,
        fixed_only: bool = False,
    ) -> np.ndarray:
        """Score pre-featurized dense rows. ``features`` maps every shard
        the model uses to a (B, d_shard) array; ``entity_ids`` maps each
        random-effect type to (B,) int32 indices (-1 = unknown). With
        ``fixed_only`` the random-effect/factored coordinates are skipped
        (degraded mode: every row scores as if cold-start). Returns
        (B,) float scores (+ offsets when given)."""
        return self._score(None, features, entity_ids, offsets, fixed_only)

    def score(
        self, requests: Sequence[ScoreRequest], fixed_only: bool = False
    ) -> np.ndarray:
        """Featurize and score a batch of requests (scores include each
        request's offset). ``fixed_only`` is the degraded serving mode:
        random effects are skipped, every request scores like cold-start."""
        return self._score(requests, None, None, None, fixed_only)

    def _score(self, requests, features, entity_ids, offsets, fixed_only):
        """One batch under its spans: ``serving.score`` is the parent of
        ``serving.featurize`` (request objects to padded arrays, entity
        translation, the executable looked up), ``serving.dispatch`` (the
        compiled call, until it returns) and ``serving.fetch`` (until the
        scores are on the host). Inside the micro-batcher every one of
        them inherits ``batch_id`` from the ambient span context."""
        with obs.span(
            "serving.score", cat="serving", fixed_only=fixed_only
        ) as sp:
            with obs.span("serving.featurize", cat="serving") as fsp:
                if requests is not None:
                    features, entity_ids, offsets = self.featurize(requests)
                plan = self._prepare(features, entity_ids or {}, fixed_only)
                fsp.set(rows=plan.rows, bucket=plan.bucket)
            sp.set(rows=plan.rows, bucket=plan.bucket, **plan.attrs)
            t0 = time.perf_counter()
            with obs.span("serving.dispatch", cat="serving"):
                on_device = plan.call()
            with obs.span("serving.fetch", cat="serving"):
                on_host = np.asarray(on_device)
            out = plan.finish(on_host)
            if plan.action.corrupt:
                out = np.full_like(out, np.nan)
            # the np.asarray above synchronized, so the window is true
            # dispatch-to-done time. Per-bucket device latency: the
            # aggregate device_ms histogram cannot say WHICH padded size
            # is slow
            plan.record(time.perf_counter() - t0)
        if offsets is not None:
            out = out + np.asarray(offsets, out.dtype)
        if self.drift is not None and not fixed_only:
            # sample this batch's (unpadded) features + scores into the
            # live drift window. Degraded batches are skipped — fixed-
            # effect-only scores are a different distribution by design
            # and would read as model drift.
            self.drift.observe(
                {s: np.asarray(features[s]) for s in self._used_shards},
                out,
            )
        return out

    def _prepare(self, features, entity_ids, fixed_only) -> "_ScorePlan":
        """Host-side half of one batch: validate, pad to the bucket,
        translate entity ids, find the bucket's executable."""
        missing = [s for s in self._used_shards if s not in features]
        if missing:
            raise KeyError(f"missing feature shard(s): {missing}")
        n = int(np.shape(features[self._used_shards[0]])[0])
        bucket = bucket_size(n, self.min_bucket)
        # chaos seam: device scoring. raise-mode surfaces through the
        # batcher to the request futures (engine state untouched, the
        # NEXT batch scores clean); delay-mode is the tail-latency drill;
        # corrupt-mode poisons the scores with NaN (a device/table
        # corruption simulant callers must be able to observe).
        action = _faults.fire("serving.score", key=str(bucket))
        feats_p = {
            s: _pad_rows(np.asarray(features[s], self.dtype), bucket)
            for s in self._used_shards
        }
        ents_p = {}
        params = self._params
        unknown = 0
        if not fixed_only:
            translated, params = self._translate_entities(entity_ids)
            for rk in self._re_keys:
                col = translated.get(rk)
                col = (
                    np.full(n, -1, np.int32)
                    if col is None
                    else np.asarray(col, np.int32)
                )
                # rows scoring cold-start on this RE type: the per-trace
                # timeline needs this to explain a degraded-looking score
                # without any fixed_only/cache-miss event in sight
                unknown += int(np.count_nonzero(col < 0))
                ents_p[rk] = _pad_rows(col, bucket, fill=-1)
        compiled = self._ensure_compiled(
            bucket,
            {s: feats_p[s].shape[1] for s in self._used_shards},
            fixed_only=fixed_only,
        )
        args = (params, feats_p) if fixed_only else (params, feats_p, ents_p)
        return _ScorePlan(
            rows=n,
            bucket=bucket,
            action=action,
            attrs={"unknown_entities": unknown},
            call=lambda: compiled(*args),
            finish=lambda host: host[:n],
            record=lambda elapsed: self.stats.record_bucket_latency(
                bucket, elapsed
            ),
        )

    def score_data(self, data: GameData) -> np.ndarray:
        """Score a dense-sharded :class:`GameData` through the bucketed
        online path; returns margins WITHOUT offsets — directly comparable
        to ``score_game_data`` on the same data."""
        from photon_ml_tpu.ops.sparse import is_structured

        for s in self._used_shards:
            if is_structured(data.features[s]):
                raise ValueError(
                    f"shard {s!r}: the online engine featurizes densely; "
                    "score structured shards through score_game_data"
                )
        feats = {s: np.asarray(data.features[s]) for s in self._used_shards}
        return self.score_arrays(feats, dict(data.entity_ids))
