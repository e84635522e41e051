"""Native (C++) Avro ingest: equivalence against the Python codec.

The native decoder must be a pure fast path: every artifact it produces
(vocabulary, LabeledBatch, GameData, uids, label flags) must match the
Python-codec path bit-for-bit on the same files — the analog of the
reference's executor-side parse being exercised through
``DriverIntegTest``-style fixtures (SURVEY §4).
"""

import os

import numpy as np
import pytest

from photon_ml_tpu.io.avro import write_avro_file
from photon_ml_tpu.io.ingest import (
    RESPONSE_PREDICTION_FIELDS,
    IngestSource,
    make_training_example,
)
from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA
from photon_ml_tpu.io.vocab import FeatureVocabulary

native = pytest.importorskip("photon_ml_tpu.io.native")

pytestmark = pytest.mark.skipif(
    not native.native_available(),
    reason=f"native reader unavailable: {native.native_error()}",
)


def _records(n, d=200, seed=0, with_meta=True, null_labels=False):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        feats = {
            (f"f{j}", "t"): float(rng.standard_normal())
            for j in rng.choice(d, min(8, d), replace=False)
        }
        # one duplicate (name, term) per third record: dedup-by-sum cover
        if i % 3 == 0:
            k = next(iter(feats))
            rec_feats = list(feats.items()) + [(k, 0.5)]
        else:
            rec_feats = list(feats.items())
        rec = make_training_example(
            label=float(rng.integers(0, 2)),
            features={},
            uid=f"u{i}" if i % 3 else None,
            offset=float(rng.standard_normal()) if i % 2 else None,
            weight=float(rng.uniform(0.5, 2.0)) if i % 5 else None,
        )
        rec["features"] = [
            {"name": nm, "term": t, "value": float(v)}
            for (nm, t), v in rec_feats
        ]
        if with_meta:
            rec["metadataMap"] = (
                {"userId": f"user{i % 11}", "songId": f"s{i % 7}"}
                if i % 4
                else None
            )
        if null_labels and i % 2:
            rec["label"] = None
        out.append(rec)
    return out


def _force_fallback(source: IngestSource) -> IngestSource:
    source._native = lambda: None  # type: ignore[method-assign]
    return source


@pytest.fixture()
def avro_file(tmp_path):
    recs = _records(600)
    path = str(tmp_path / "part-0.avro")
    write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs, codec="deflate")
    return path, recs


class TestLabeledBatch:
    @pytest.mark.parametrize("sparse", [False, True])
    def test_matches_python_path(self, avro_file, sparse):
        path, _ = avro_file
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        nat = IngestSource([path]).labeled_batch(vocab, sparse=sparse)
        ref = _force_fallback(IngestSource([path])).labeled_batch(
            vocab, sparse=sparse
        )
        for a, b in zip(nat[:1], ref[:1]):
            if sparse:
                from photon_ml_tpu.ops.sparse import to_dense

                np.testing.assert_allclose(
                    to_dense(a.features), to_dense(b.features), rtol=1e-6
                )
            else:
                np.testing.assert_allclose(
                    np.asarray(a.features), np.asarray(b.features),
                    rtol=1e-6,
                )
            np.testing.assert_array_equal(
                np.asarray(a.labels), np.asarray(b.labels)
            )
            np.testing.assert_array_equal(
                np.asarray(a.offsets), np.asarray(b.offsets)
            )
            np.testing.assert_array_equal(
                np.asarray(a.weights), np.asarray(b.weights)
            )
        assert list(nat[1]) == list(ref[1])  # uids incl. None
        np.testing.assert_array_equal(nat[2], ref[2])

    def test_streamed_matches_whole_read(self, tmp_path):
        """labeled_batch_streamed (per-file decode + async device
        transfers) must assemble the identical batch the
        whole-dataset path builds, across multiple part files with
        different row counts."""
        paths = []
        for i, n in enumerate([150, 90, 200]):
            recs = _records(n, seed=10 + i)
            p = str(tmp_path / f"part-{i}.avro")
            write_avro_file(p, TRAINING_EXAMPLE_SCHEMA, recs, codec="deflate")
            paths.append(p)
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        whole = IngestSource(paths).labeled_batch(vocab)
        streamed = IngestSource(paths).labeled_batch_streamed(vocab)
        np.testing.assert_allclose(
            np.asarray(streamed[0].features),
            np.asarray(whole[0].features),
            rtol=1e-6,
        )
        for field in ("labels", "offsets", "weights", "mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(streamed[0], field)),
                np.asarray(getattr(whole[0], field)),
            )
        assert list(streamed[1]) == list(whole[1])
        np.testing.assert_array_equal(streamed[2], whole[2])

        # the streamed batch trains like any other
        from photon_ml_tpu.models import (
            GLMTrainingConfig,
            OptimizerType,
            TaskType,
            train_glm,
        )
        from photon_ml_tpu.ops.objective import RegularizationContext

        cfg = GLMTrainingConfig(
            task=TaskType.LOGISTIC_REGRESSION,
            optimizer=OptimizerType.LBFGS,
            regularization=RegularizationContext("L2"),
            reg_weights=(1.0,),
            max_iters=15,
            track_states=False,
        )
        (a,) = train_glm(streamed[0], cfg)
        (b,) = train_glm(whole[0], cfg)
        np.testing.assert_allclose(
            np.asarray(a.model.coefficients.means),
            np.asarray(b.model.coefficients.means),
            atol=1e-10,
        )

    def test_streamed_assembly_hbm_watermark(self, tmp_path, monkeypatch):
        """The streamed assembly is bracketed by the new HBM telemetry:
        an ``hbm.watermark`` event labeled ``io.ingest.assemble`` (plus
        peak/delta gauges) lands whenever the platform reports memory
        stats — scripted here, since CPU reports none — making the
        dataset-plus-one-chunk peak contract of the destructive chunk
        consumption observable instead of assumed."""
        import json as _json
        import os as _os

        from photon_ml_tpu import obs
        from photon_ml_tpu.obs import device as device_mod
        from photon_ml_tpu.obs.metrics import MetricsRegistry

        calls = {"n": 0}

        def fake_stats(device=None):
            calls["n"] += 1
            return {
                "bytes_in_use": 1000 * calls["n"],
                "peak_bytes_in_use": 1000 * calls["n"],
            }

        monkeypatch.setattr(device_mod, "read_memory_stats", fake_stats)

        paths = []
        for i, n in enumerate([80, 50]):
            recs = _records(n, seed=30 + i)
            p = str(tmp_path / f"part-{i}.avro")
            write_avro_file(p, TRAINING_EXAMPLE_SCHEMA, recs)
            paths.append(p)
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        reg = MetricsRegistry()
        prev = obs.set_registry(reg)
        tdir = str(tmp_path / "trace")
        try:
            with obs.trace(tdir):
                batch, _, _ = IngestSource(paths).labeled_batch_streamed(
                    vocab
                )
        finally:
            obs.set_registry(prev)
        assert batch.num_features == 201
        events = [
            _json.loads(line)
            for line in open(_os.path.join(tdir, "events.jsonl"))
        ]
        marks = [
            e
            for e in events
            if e.get("name") == "hbm.watermark"
            and e.get("label") == "io.ingest.assemble"
        ]
        assert len(marks) == 1
        assert marks[0]["peak_bytes"] > 0
        assert marks[0]["delta_bytes"] == (
            marks[0]["after_bytes"] - marks[0]["before_bytes"]
        )
        gauges = reg.snapshot()["gauges"]
        assert "hbm.io.ingest.assemble.peak_bytes" in gauges
        assert "hbm.io.ingest.assemble.delta_bytes" in gauges

    def test_tiny_vocab(self, tmp_path):
        """Vocabulary blobs short enough for std::string SSO — regression
        for the in-place Vocab construction (a moved SSO string dangles
        every string_view into it)."""
        recs = _records(60, d=4)
        path = str(tmp_path / "tiny.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs)
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(4)], add_intercept=False
        )
        nat = IngestSource([path]).labeled_batch(vocab)
        ref = _force_fallback(IngestSource([path])).labeled_batch(vocab)
        np.testing.assert_allclose(
            np.asarray(nat[0].features), np.asarray(ref[0].features)
        )

    def test_null_codec(self, tmp_path):
        recs = _records(50)
        path = str(tmp_path / "plain.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs, codec="null")
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        nat = IngestSource([path]).labeled_batch(vocab)
        ref = _force_fallback(IngestSource([path])).labeled_batch(vocab)
        np.testing.assert_allclose(
            np.asarray(nat[0].features), np.asarray(ref[0].features)
        )

    def test_null_label_policy(self, tmp_path):
        """Training input refuses null labels; scoring coerces to 0."""
        schema = dict(TRAINING_EXAMPLE_SCHEMA)
        schema["fields"] = [
            (
                {"name": "label", "type": ["null", "double"], "default": None}
                if f["name"] == "label"
                else f
            )
            for f in TRAINING_EXAMPLE_SCHEMA["fields"]
        ]
        recs = _records(40, null_labels=True)
        path = str(tmp_path / "nulls.avro")
        write_avro_file(path, schema, recs)
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        with pytest.raises(ValueError, match="null/missing label"):
            IngestSource([path]).labeled_batch(vocab)
        batch, _, present = IngestSource([path]).labeled_batch(
            vocab, allow_null_labels=True
        )
        assert not present.all() and present.any()
        labels = np.asarray(batch.labels)
        assert (labels[~present] == 0.0).all()


class TestGameData:
    def test_matches_python_path(self, avro_file):
        path, _ = avro_file
        vocab_a = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(120)], add_intercept=True
        )
        vocab_b = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(80, 200)], add_intercept=False
        )
        shard_vocabs = {"shardA": vocab_a, "shardB": vocab_b}
        keys = ["userId", "songId"]
        nat = IngestSource([path]).game_data(shard_vocabs, keys)
        ref = _force_fallback(IngestSource([path])).game_data(
            shard_vocabs, keys
        )
        for shard in shard_vocabs:
            np.testing.assert_allclose(
                np.asarray(nat[0].features[shard]),
                np.asarray(ref[0].features[shard]),
                rtol=1e-6,
            )
        for k in keys:
            np.testing.assert_array_equal(
                np.asarray(nat[0].entity_ids[k]),
                np.asarray(ref[0].entity_ids[k]),
            )
            assert nat[1][k] == ref[1][k]
        np.testing.assert_array_equal(
            np.asarray(nat[0].labels), np.asarray(ref[0].labels)
        )
        assert list(nat[2]) == list(ref[2])

    def test_applied_entity_vocab(self, avro_file):
        """Scoring mode: a trained model's entity vocab is applied; unknown
        entities map to -1 semantics via apply_entity_vocabulary."""
        path, _ = avro_file
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        given = {"userId": {f"user{i}": i for i in range(5)}}
        nat = IngestSource([path]).game_data(
            {"s": vocab}, ["userId"], entity_vocabs=given
        )
        ref = _force_fallback(IngestSource([path])).game_data(
            {"s": vocab}, ["userId"], entity_vocabs=given
        )
        np.testing.assert_array_equal(
            np.asarray(nat[0].entity_ids["userId"]),
            np.asarray(ref[0].entity_ids["userId"]),
        )


class TestVocabScan:
    def test_matches_from_records(self, avro_file):
        path, recs = avro_file
        nat = IngestSource([path]).build_vocab(add_intercept=True)
        ref = FeatureVocabulary.from_records(recs, add_intercept=True)
        assert nat.index_to_key == ref.index_to_key

    def test_selected_keys_filter(self, avro_file):
        path, recs = avro_file
        selected = {f"f{i}\x01t" for i in range(0, 200, 2)}
        nat = IngestSource([path]).build_vocab(selected_keys=selected)
        ref = FeatureVocabulary.from_records(recs, selected_keys=selected)
        assert nat.index_to_key == ref.index_to_key


class TestFieldNameSets:
    def test_response_prediction(self, tmp_path):
        """RESPONSE_PREDICTION reads "response" as the label
        (``avro/ResponsePredictionFieldNames.scala``)."""
        schema = {
            "name": "ResponsePredictionAvro",
            "type": "record",
            "fields": [
                {"name": "response", "type": "double"},
                {
                    "name": "features",
                    "type": {
                        "type": "array",
                        "items": {
                            "name": "F",
                            "type": "record",
                            "fields": [
                                {"name": "name", "type": "string"},
                                {"name": "term", "type": "string"},
                                {"name": "value", "type": "double"},
                            ],
                        },
                    },
                },
            ],
        }
        recs = [
            {
                "response": float(i % 2),
                "features": [
                    {"name": f"f{i % 7}", "term": "", "value": 1.0 + i}
                ],
            }
            for i in range(30)
        ]
        path = str(tmp_path / "resp.avro")
        write_avro_file(path, schema, recs)
        vocab = FeatureVocabulary(
            [f"f{i}\x01" for i in range(7)], add_intercept=False
        )
        src = IngestSource([path], field_names=RESPONSE_PREDICTION_FIELDS)
        nat = src.labeled_batch(vocab)
        ref = _force_fallback(
            IngestSource([path], field_names=RESPONSE_PREDICTION_FIELDS)
        ).labeled_batch(vocab)
        np.testing.assert_array_equal(
            np.asarray(nat[0].labels), np.asarray(ref[0].labels)
        )
        np.testing.assert_allclose(
            np.asarray(nat[0].features), np.asarray(ref[0].features)
        )


class TestStringEdgeCases:
    def test_non_ascii_strings(self, tmp_path):
        """Multi-byte UTF-8 in uids, entity ids, and feature names must
        round-trip exactly (byte offsets vs character offsets)."""
        recs = []
        for i in range(12):
            recs.append(
                make_training_example(
                    label=float(i % 2),
                    features={(f"caffé{i % 3}", "tèrm"): 1.0 + i},
                    uid=f"usér{i}" if i % 2 else None,
                )
            )
            recs[-1]["metadataMap"] = {"userId": f"ü{i % 4}"}
        path = str(tmp_path / "utf8.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs)
        vocab = IngestSource([path]).build_vocab(add_intercept=False)
        ref_vocab = _force_fallback(IngestSource([path])).build_vocab(
            add_intercept=False
        )
        assert vocab.index_to_key == ref_vocab.index_to_key
        nat = IngestSource([path]).game_data({"s": vocab}, ["userId"])
        ref = _force_fallback(IngestSource([path])).game_data(
            {"s": ref_vocab}, ["userId"]
        )
        np.testing.assert_allclose(
            np.asarray(nat[0].features["s"]), np.asarray(ref[0].features["s"])
        )
        assert nat[1]["userId"] == ref[1]["userId"]
        assert list(nat[2]) == list(ref[2])  # uids

    def test_newline_in_feature_name(self, tmp_path):
        """Keys travel as offset-framed bytes, so embedded newlines cannot
        split or shift the vocabulary."""
        recs = [
            make_training_example(
                label=1.0,
                features={("a\nb", "t"): 7.0, ("c", "t"): 9.0},
            )
        ]
        path = str(tmp_path / "nl.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs)
        vocab = FeatureVocabulary(
            ["a\nb\x01t", "c\x01t"], add_intercept=False
        )
        nat = IngestSource([path]).labeled_batch(vocab)
        np.testing.assert_allclose(
            np.asarray(nat[0].features), [[7.0, 9.0]]
        )


class TestEmptyInput:
    def test_empty_file_raises(self, tmp_path):
        path = str(tmp_path / "empty.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, [])
        vocab = FeatureVocabulary(["f0\x01t"], add_intercept=False)
        with pytest.raises(ValueError, match="no records found"):
            IngestSource([path]).labeled_batch(vocab)
        with pytest.raises(ValueError, match="no records found"):
            _force_fallback(IngestSource([path])).labeled_batch(vocab)


class TestEmptyVocabScan:
    def test_empty_file_build_vocab_raises(self, tmp_path):
        """A valid-but-empty input must fail build_vocab loudly on BOTH
        toolchains — the native scan must not silently yield an
        intercept-only vocabulary."""
        path = str(tmp_path / "empty.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, [])
        with pytest.raises(ValueError, match="no records found"):
            IngestSource([path]).build_vocab()
        with pytest.raises(ValueError, match="no records found"):
            _force_fallback(IngestSource([path])).build_vocab()


class TestThreadedBlockDecode:
    """Within-file block-parallel decode (the within-host analog of the
    reference's executor-parallel Avro parse) must produce output
    bit-identical to the sequential read."""

    @pytest.mark.parametrize("codec", ["null", "deflate"])
    def test_matches_sequential(self, tmp_path, codec):
        recs = _records(900, seed=5)
        path = str(tmp_path / "blocks.avro")
        # small blocks so the file has ~15 of them to spread over threads
        write_avro_file(
            path, TRAINING_EXAMPLE_SCHEMA, recs, codec=codec, block_size=64
        )
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        seq = native.read_columnar(
            [path], [vocab], ["userId", "songId"], decode_threads=1
        )
        mt = native.read_columnar(
            [path], [vocab], ["userId", "songId"], decode_threads=4
        )
        assert seq["n"] == mt["n"] == 900
        for k in ("labels", "label_present", "offsets", "weights"):
            np.testing.assert_array_equal(seq[k], mt[k])
        np.testing.assert_array_equal(seq["uids"], mt["uids"])
        for key in ("userId", "songId"):
            np.testing.assert_array_equal(
                seq["entities"][key], mt["entities"][key]
            )
        for a, b in zip(seq["coo"], mt["coo"]):
            for x, y in zip(a, b):
                np.testing.assert_array_equal(x, y)

    def test_threaded_scan_matches(self, tmp_path):
        recs = _records(600, seed=9)
        path = str(tmp_path / "blocks.avro")
        write_avro_file(
            path, TRAINING_EXAMPLE_SCHEMA, recs, block_size=50
        )
        k1, n1 = native.scan_feature_keys([path])
        # _default_decode_threads drives the threaded path internally; force
        # a reader-level check too
        schema = native._read_header_schema(path)
        prog, fd = native.compile_schema(schema)
        vs = native.NativeVocabSet([], [])
        try:
            r = native.NativeAvroReader(prog, fd, vs, (), collect_keys=True)
            r.feed_file(path, decode_threads=4)
            k4 = r.distinct_keys()
            assert r.num_records == n1 == 600
            r.close()
        finally:
            vs.close()
        assert sorted(k1) == sorted(k4)


class TestParallelFiles:
    def test_multi_file_parallel_matches_fallback(self, tmp_path):
        """4 part files decode in parallel threads; row order must equal
        the sequential Python-codec read (path order)."""
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        paths = []
        for part in range(4):
            recs = _records(120, seed=100 + part)
            p = str(tmp_path / f"part-{part}.avro")
            write_avro_file(p, TRAINING_EXAMPLE_SCHEMA, recs)
            paths.append(p)
        nat = IngestSource(paths).labeled_batch(vocab)
        ref = _force_fallback(IngestSource(paths)).labeled_batch(vocab)
        np.testing.assert_allclose(
            np.asarray(nat[0].features), np.asarray(ref[0].features),
            rtol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(nat[0].labels), np.asarray(ref[0].labels)
        )
        assert list(nat[1]) == list(ref[1])
        # entity columns concatenate in order too
        nat_g = IngestSource(paths).game_data({"s": vocab}, ["userId"])
        ref_g = _force_fallback(IngestSource(paths)).game_data(
            {"s": vocab}, ["userId"]
        )
        np.testing.assert_array_equal(
            np.asarray(nat_g[0].entity_ids["userId"]),
            np.asarray(ref_g[0].entity_ids["userId"]),
        )
        # parallel vocabulary scan unions per-file keysets
        nat_v = IngestSource(paths).build_vocab()
        ref_v = _force_fallback(IngestSource(paths)).build_vocab()
        assert nat_v.index_to_key == ref_v.index_to_key


class TestCorruptInput:
    """A native decoder must fail CLEANLY on malformed bytes — raise a
    Python exception, never crash or mis-decode silently."""

    @pytest.fixture()
    def valid_file(self, tmp_path):
        recs = _records(40)
        path = str(tmp_path / "ok.avro")
        write_avro_file(path, TRAINING_EXAMPLE_SCHEMA, recs, codec="deflate")
        return path

    def _vocab(self):
        return FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )

    def test_truncated_everywhere(self, valid_file, tmp_path):
        raw = open(valid_file, "rb").read()
        # cut points: inside header, inside block framing, inside payload
        for frac in (0.05, 0.3, 0.6, 0.9, 0.99):
            cut = int(len(raw) * frac)
            p = str(tmp_path / f"cut{cut}.avro")
            with open(p, "wb") as f:
                f.write(raw[:cut])
            with pytest.raises((ValueError, EOFError, KeyError)):
                native.read_columnar([p], [self._vocab()])

    def test_flipped_payload_bytes(self, valid_file, tmp_path):
        raw = bytearray(open(valid_file, "rb").read())
        # corrupt deflate payload mid-file: decompression or sync check
        # must catch it
        mid = len(raw) // 2
        for i in range(mid, min(mid + 40, len(raw))):
            raw[i] ^= 0xFF
        p = str(tmp_path / "flip.avro")
        with open(p, "wb") as f:
            f.write(bytes(raw))
        with pytest.raises(ValueError):
            native.read_columnar([p], [self._vocab()])

    def test_bad_magic(self, tmp_path):
        p = str(tmp_path / "junk.avro")
        with open(p, "wb") as f:
            f.write(b"NOPE" + b"\x00" * 64)
        with pytest.raises(ValueError, match="not an Avro container"):
            native.read_columnar([p], [self._vocab()])

    def test_lying_block_count(self, valid_file, tmp_path):
        """A block declaring more records than its payload holds must
        error (the C++ Slice guards), not read out of bounds."""
        from photon_ml_tpu.io.avro import (
            MAGIC,
            _decode_bytes,
            _decode_long,
            _encode_long,
        )
        import io as _io

        raw = open(valid_file, "rb").read()
        buf = _io.BytesIO(raw)
        assert buf.read(4) == MAGIC
        while True:
            count = _decode_long(buf)
            if count == 0:
                break
            for _ in range(count):
                _decode_bytes(buf)
                _decode_bytes(buf)
        buf.read(16)
        header_end = buf.tell()
        block_count = _decode_long(buf)
        rest_pos = buf.tell()
        forged = (
            raw[:header_end]
            + _encode_long(block_count * 1000)
            + raw[rest_pos:]
        )
        p = str(tmp_path / "forged.avro")
        with open(p, "wb") as f:
            f.write(forged)
        with pytest.raises(ValueError, match="native decode failed"):
            native.read_columnar([p], [self._vocab()])


class TestNativeWriter:
    def _roundtrip(self, tmp_path, codec):
        from photon_ml_tpu.io.avro import read_avro_file
        from photon_ml_tpu.io.native import write_columnar_avro
        from photon_ml_tpu.io.schemas import SCORING_RESULT_SCHEMA

        n = 10_000
        rng = np.random.default_rng(4)
        scores = rng.standard_normal(n)
        labels = rng.integers(0, 2, n).astype(np.float64)
        present = (np.arange(n) % 3 != 0)
        uids = np.asarray(
            [None if i % 5 == 0 else f"usér{i}" for i in range(n)], object
        )
        path = str(tmp_path / f"scores_{codec}.avro")
        write_columnar_avro(
            path,
            SCORING_RESULT_SCHEMA,
            {
                "predictionScore": scores,
                "uid": uids,
                "label": (labels, present),
                "metadataMap": None,
            },
            n,
            codec=codec,
        )
        # the PYTHON codec must read the native file (cross-codec check)
        _, recs = read_avro_file(path)
        assert len(recs) == n
        np.testing.assert_allclose(
            [r["predictionScore"] for r in recs], scores
        )
        for i in (0, 1, 3, 5, 4999, n - 1):
            assert recs[i]["uid"] == uids[i]
            expected = float(labels[i]) if present[i] else None
            assert recs[i]["label"] == expected
            assert recs[i]["metadataMap"] is None

    def test_roundtrip_deflate(self, tmp_path):
        self._roundtrip(tmp_path, "deflate")

    def test_roundtrip_null_codec(self, tmp_path):
        self._roundtrip(tmp_path, "null")

    def test_native_reader_reads_native_writer(self, tmp_path):
        """Both ends native: the scoring output is valid scoring INPUT
        (label-bearing rows evaluate, null-label rows coerce)."""
        from photon_ml_tpu.io.native import write_columnar_avro

        schema = {
            "name": "Flat",
            "type": "record",
            "fields": [
                {"name": "label", "type": ["null", "double"], "default": None},
                {"name": "weight", "type": "double"},
            ],
        }
        n = 50
        labels = np.arange(n, dtype=np.float64)
        present = np.ones(n, bool)
        present[7] = False
        path = str(tmp_path / "flat.avro")
        write_columnar_avro(
            path, schema,
            {"label": (labels, present), "weight": labels * 2}, n,
        )
        from photon_ml_tpu.io.avro import read_avro_file

        _, recs = read_avro_file(path)
        assert recs[7]["label"] is None
        assert recs[8]["label"] == 8.0
        assert recs[9]["weight"] == 18.0

    def test_float_fields_roundtrip(self, tmp_path):
        """float / [null, float] fields take the 4-byte wire op — a
        double-width encode silently corrupted these (1.5
        read back as 0.0)."""
        from photon_ml_tpu.io.avro import read_avro_file
        from photon_ml_tpu.io.native import write_columnar_avro

        schema = {
            "name": "F",
            "type": "record",
            "fields": [
                {"name": "x", "type": "float"},
                {"name": "y", "type": ["null", "float"], "default": None},
                {"name": "z", "type": "double"},
            ],
        }
        n = 100
        rng = np.random.default_rng(11)
        x = rng.standard_normal(n)
        y = rng.standard_normal(n)
        present = np.arange(n) % 4 != 0
        z = rng.standard_normal(n)
        path = str(tmp_path / "floats.avro")
        write_columnar_avro(
            path, schema, {"x": x, "y": (y, present), "z": z}, n
        )
        _, recs = read_avro_file(path)
        np.testing.assert_allclose(
            [r["x"] for r in recs], x.astype(np.float32), rtol=1e-6
        )
        np.testing.assert_allclose([r["z"] for r in recs], z)
        for i in (0, 1, 2, 3, 4, 99):
            if present[i]:
                assert abs(recs[i]["y"] - float(np.float32(y[i]))) < 1e-6
            else:
                assert recs[i]["y"] is None

    def test_writer_failure_falls_back_with_log(self, tmp_path, caplog):
        """A native-writer failure must fall back to the Python codec AND
        leave a log record — never silently (cli/score.py contract)."""
        import logging

        from photon_ml_tpu.cli.score import write_scored_items
        from photon_ml_tpu.io import native as native_mod
        from photon_ml_tpu.io.avro import read_avro_file

        n = 20
        scores = np.arange(n, dtype=np.float64)
        uids = np.asarray([f"u{i}" for i in range(n)], object)
        labels = np.ones(n)
        present = np.ones(n, bool)
        out = str(tmp_path / "scores.avro")

        def boom(*a, **k):
            raise IOError("native Avro write failed (rc=-4)")

        orig = native_mod.write_columnar_avro
        native_mod.write_columnar_avro = boom
        try:
            with caplog.at_level(logging.WARNING, "photon_ml_tpu"):
                wrote = write_scored_items(out, scores, uids, labels, present)
        finally:
            native_mod.write_columnar_avro = orig
        assert wrote == n
        assert any(
            "native Avro writer failed" in r.message for r in caplog.records
        )
        _, recs = read_avro_file(out)
        assert [r["predictionScore"] for r in recs] == list(scores)

    def test_unsupported_write_schema(self, tmp_path):
        from photon_ml_tpu.io.native import write_columnar_avro
        from photon_ml_tpu.io.schemas import TRAINING_EXAMPLE_SCHEMA

        with pytest.raises(native.UnsupportedSchema):
            write_columnar_avro(
                str(tmp_path / "x.avro"), TRAINING_EXAMPLE_SCHEMA, {}, 0
            )


class TestSchemaFuzz:
    """Seeded random schemas in the supported family: the compiled native
    program must agree with the schema-general Python codec on every
    generated layout (field order, optional-ness, union branch order,
    extra skipped fields)."""

    @pytest.mark.parametrize("seed", range(6))
    def test_random_flat_schema_equivalence(self, tmp_path, seed):
        rng = np.random.default_rng(seed)

        def maybe_optional(t):
            r = rng.integers(0, 3)
            if r == 0:
                return t, False
            if r == 1:
                return ["null", t], True
            return [t, "null"], True

        fields = []
        makers = {}
        feat_fields = [
            {"name": "name", "type": "string"},
            {"name": "term", "type": "string"},
            {"name": "value", "type": "double"},
        ]
        rng.shuffle(feat_fields)
        # core fields in random order, plus skippable extras
        core = [
            ("label", "double"),
            ("offset", "double"),
            ("weight", "double"),
            ("uid", "string"),
            ("features", None),
        ]
        extras = [
            (f"extra{i}", rng.choice(["double", "long", "string", "boolean"]))
            for i in range(rng.integers(0, 3))
        ]
        order = core + extras
        rng.shuffle(order)
        for fname, ftype in order:
            if fname == "features":
                fields.append(
                    {
                        "name": "features",
                        "type": {
                            "type": "array",
                            "items": {
                                "name": f"F{seed}",
                                "type": "record",
                                "fields": feat_fields,
                            },
                        },
                    }
                )
                continue
            t, optional = maybe_optional(str(ftype))
            fields.append({"name": fname, "type": t})
            makers[fname] = (ftype, optional)
        schema = {"name": f"Fuzz{seed}", "type": "record", "fields": fields}

        def value_of(ftype, i):
            if ftype == "double":
                return float(i) * 0.5
            if ftype == "long":
                return int(i)
            if ftype == "boolean":
                return bool(i % 2)
            return f"s{i}"

        recs = []
        for i in range(40):
            rec = {
                "features": [
                    {
                        "name": f"f{int(j)}",
                        "term": "t",
                        "value": float(i + j),
                    }
                    for j in rng.choice(20, 3, replace=False)
                ]
            }
            for fname, (ftype, optional) in makers.items():
                if optional and i % 3 == 0:
                    rec[fname] = None
                else:
                    rec[fname] = value_of(ftype, i)
            recs.append(rec)
        path = str(tmp_path / f"fuzz{seed}.avro")
        write_avro_file(path, schema, recs)
        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(20)], add_intercept=False
        )
        try:
            nat = IngestSource([path]).labeled_batch(
                vocab, allow_null_labels=True
            )
        except native.UnsupportedSchema:
            return  # honest refusal is fine; silence would not be
        ref = _force_fallback(IngestSource([path])).labeled_batch(
            vocab, allow_null_labels=True
        )
        np.testing.assert_allclose(
            np.asarray(nat[0].features), np.asarray(ref[0].features),
            rtol=1e-6,
        )
        np.testing.assert_array_equal(
            np.asarray(nat[0].labels), np.asarray(ref[0].labels)
        )
        np.testing.assert_array_equal(
            np.asarray(nat[0].offsets), np.asarray(ref[0].offsets)
        )
        np.testing.assert_array_equal(
            np.asarray(nat[0].weights), np.asarray(ref[0].weights)
        )
        np.testing.assert_array_equal(nat[2], ref[2])


class TestSchemaGuards:
    def test_mixed_schema_files_fall_back(self, tmp_path):
        """Files with different writer schemas can't share one compiled
        program; IngestSource must still produce correct output (via the
        Python codec), not misdecode."""
        recs_a = _records(20, seed=1)
        path_a = str(tmp_path / "a.avro")
        write_avro_file(path_a, TRAINING_EXAMPLE_SCHEMA, recs_a)
        schema_b = dict(TRAINING_EXAMPLE_SCHEMA)
        schema_b["fields"] = [
            f
            for f in TRAINING_EXAMPLE_SCHEMA["fields"]
            if f["name"] != "weight"
        ]
        recs_b = _records(20, seed=2)
        for r in recs_b:
            r.pop("weight", None)
        path_b = str(tmp_path / "b.avro")
        write_avro_file(path_b, schema_b, recs_b)

        vocab = FeatureVocabulary(
            [f"f{i}\x01t" for i in range(200)], add_intercept=True
        )
        nat = IngestSource([path_a, path_b]).labeled_batch(vocab)
        ref = _force_fallback(
            IngestSource([path_a, path_b])
        ).labeled_batch(vocab)
        np.testing.assert_allclose(
            np.asarray(nat[0].features), np.asarray(ref[0].features)
        )
        np.testing.assert_array_equal(
            np.asarray(nat[0].weights), np.asarray(ref[0].weights)
        )

    def test_unsupported_schema_compile(self):
        with pytest.raises(native.UnsupportedSchema):
            native.compile_schema({"type": "record", "fields": []})
