"""QuantileDigest (utils/digest.py): the SLO observatory's estimator.

The contract every consumer leans on (serving/slo.py, perfwatch,
the metrics summary kind):

* relative-error bound vs exact sample percentiles — on uniform, Zipf,
  bimodal and adversarial streams,
* merge associativity — sketching shards and merging equals sketching
  the concatenated stream,
* fixed memory under 10M inserts (upper quantiles keep the bound after
  the collapse rule fires),
* exact serialize/deserialize roundtrip (a snapshot carries the sketch
  itself, so the roundtrip must not be lossy).

jax-free on purpose: this estimator runs on CI boxes and in perfwatch.
"""

import json
import math

import numpy as np
import pytest

from code_intelligence_tpu.utils.digest import MIN_TRACKABLE, QuantileDigest

QS = (0.5, 0.9, 0.99, 0.999)


def exact(a: np.ndarray, q: float) -> float:
    """The sample the digest's rank convention targets: index
    floor(q*(n-1)) of the sorted stream (numpy's 'lower' method)."""
    return float(np.percentile(a, q * 100.0, method="lower"))


def assert_within_bound(d: QuantileDigest, a: np.ndarray, qs=QS):
    for q in qs:
        est = d.quantile(q)
        true = exact(a, q)
        if true < MIN_TRACKABLE:
            assert est == 0.0
            continue
        assert abs(est - true) <= d.rel_err * true + 1e-15, (
            f"q={q}: est={est} exact={true} "
            f"rel={(abs(est - true) / true):.4%} > {d.rel_err:.2%}")


# ---------------------------------------------------------------------
# relative-error bound on characteristic streams
# ---------------------------------------------------------------------


class TestErrorBound:
    def _check(self, a, rel_err=0.01):
        d = QuantileDigest(rel_err=rel_err)
        d.add_many(a)
        assert d.count == a.size
        assert_within_bound(d, a)
        # one-at-a-time inserts land in the same buckets
        d2 = QuantileDigest(rel_err=rel_err)
        for v in a[:1000]:
            d2.add(float(v))
        assert_within_bound(d2, a[:1000])

    def test_uniform(self):
        rng = np.random.default_rng(0)
        self._check(rng.uniform(1e-3, 1.0, 50_000))

    def test_zipf_heavy_tail(self):
        # rank-frequency heavy tail: the latency shape a cache-fronted
        # serve path actually produces (many fast hits, long miss tail)
        rng = np.random.default_rng(1)
        self._check(rng.zipf(1.5, 50_000).astype(np.float64) * 1e-3)

    def test_bimodal(self):
        # hit/miss mixture: 5ms hits, 200ms device misses
        rng = np.random.default_rng(2)
        a = np.concatenate([
            np.abs(rng.normal(5e-3, 1e-3, 40_000)),
            np.abs(rng.normal(0.2, 0.02, 10_000)),
        ])
        rng.shuffle(a)
        self._check(a)

    @pytest.mark.parametrize("stream", [
        np.full(10_000, 0.25),                      # all equal
        np.sort(np.geomspace(1e-6, 10.0, 20_000)),  # ascending sweep
        np.sort(np.geomspace(1e-6, 10.0, 20_000))[::-1],  # descending
        np.geomspace(1e-6, 10.0, 20_000)[
            np.random.default_rng(3).permutation(20_000)],  # shuffled
        np.tile([1e-6, 1.0, 1e6], 5_000),           # 12-decade spikes
    ], ids=["equal", "ascending", "descending", "shuffled", "spikes"])
    def test_adversarial(self, stream):
        self._check(np.asarray(stream, np.float64))

    def test_looser_rel_err_looser_bound(self):
        rng = np.random.default_rng(4)
        self._check(rng.lognormal(-3, 1.0, 30_000), rel_err=0.05)

    def test_garbage_inputs_ignored(self):
        d = QuantileDigest()
        for v in (math.nan, math.inf, -math.inf, -1.0, -1e-12):
            d.add(v)
        assert d.count == 0 and math.isnan(d.quantile(0.5))
        d.add_many([math.nan, -5.0, 0.25, math.inf])
        assert d.count == 1 and abs(d.quantile(0.5) - 0.25) <= 0.01 * 0.25

    def test_subnanosecond_values_zero_bucket(self):
        d = QuantileDigest()
        d.add_many([0.0, 1e-12, 1e-10, 0.5])
        assert d.count == 4
        assert d.quantile(0.25) == 0.0
        assert abs(d.quantile(1.0) - 0.5) <= 0.01 * 0.5


# ---------------------------------------------------------------------
# merge
# ---------------------------------------------------------------------


class TestMerge:
    def _sketch(self, a):
        d = QuantileDigest()
        d.add_many(a)
        return d

    def test_merge_of_shards_equals_whole_stream(self):
        rng = np.random.default_rng(5)
        a = rng.lognormal(-4, 1.5, 30_000)
        whole = self._sketch(a)
        merged = QuantileDigest.merged(
            [self._sketch(s) for s in np.array_split(a, 7)])
        # identical bucketing is deterministic per value: the merge is
        # EXACT, not merely within-bound
        assert merged.to_dict()["bins"] == whole.to_dict()["bins"]
        assert merged.count == whole.count
        assert merged.min == whole.min and merged.max == whole.max
        assert merged.sum == pytest.approx(whole.sum)
        for q in QS:
            assert merged.quantile(q) == whole.quantile(q)

    def test_associativity(self):
        rng = np.random.default_rng(6)
        parts = [rng.uniform(1e-3, 1.0, 2_000) for _ in range(3)]
        ab_c = self._sketch(parts[0]).merge(self._sketch(parts[1])) \
            .merge(self._sketch(parts[2]))
        bc = self._sketch(parts[1]).merge(self._sketch(parts[2]))
        a_bc = self._sketch(parts[0]).merge(bc)
        assert ab_c.to_dict()["bins"] == a_bc.to_dict()["bins"]
        assert ab_c.count == a_bc.count

    def test_merged_leaves_inputs_untouched(self):
        # the windowed-SLO read path merges the minute ring without
        # consuming it
        a = self._sketch(np.full(100, 0.1))
        b = self._sketch(np.full(50, 0.2))
        before = (a.to_dict(), b.to_dict())
        out = QuantileDigest.merged([a, b])
        assert out.count == 150
        assert (a.to_dict(), b.to_dict()) == before

    def test_merge_with_empty(self):
        a = self._sketch(np.full(10, 0.1))
        a.merge(QuantileDigest())
        assert a.count == 10

    def test_mismatched_rel_err_refused(self):
        with pytest.raises(ValueError, match="rel_err"):
            QuantileDigest(rel_err=0.01).merge(QuantileDigest(rel_err=0.02))


# ---------------------------------------------------------------------
# fixed memory
# ---------------------------------------------------------------------


class TestFixedMemory:
    def test_ten_million_inserts_bounded(self):
        # 12 decades of dynamic range over 10M samples: thousands of
        # raw buckets, so the collapse rule MUST fire — memory stays at
        # max_bins and the upper quantiles keep their guarantee (the
        # collapse folds the LOW tail)
        rng = np.random.default_rng(7)
        a = np.exp(rng.uniform(np.log(1e-9), np.log(1e3), 10_000_000))
        d = QuantileDigest(rel_err=0.01, max_bins=512)
        for chunk in np.array_split(a, 20):
            d.add_many(chunk)
            assert d.n_bins <= 513  # max_bins + zero bucket, ALWAYS
        assert d.count == 10_000_000
        assert d.collapsed > 0  # the bound actually bit
        for q in (0.9, 0.99, 0.999):
            true = exact(a, q)
            assert abs(d.quantile(q) - true) <= d.rel_err * true

    def test_serialized_size_bounded(self):
        rng = np.random.default_rng(8)
        d = QuantileDigest(max_bins=128)
        d.add_many(np.exp(rng.uniform(np.log(1e-9), np.log(1e3), 500_000)))
        assert len(d.to_dict()["bins"]) <= 128
        assert len(json.dumps(d.to_dict())) < 64 * 1024


# ---------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------


class TestSerde:
    def test_roundtrip_exact(self):
        rng = np.random.default_rng(9)
        d = QuantileDigest(rel_err=0.02, max_bins=256)
        d.add_many(rng.lognormal(-4, 2.0, 20_000))
        back = QuantileDigest.from_dict(json.loads(json.dumps(d.to_dict())))
        assert back.to_dict() == d.to_dict()
        for q in QS:
            assert back.quantile(q) == d.quantile(q)
        assert (back.count, back.sum, back.min, back.max) == \
            (d.count, d.sum, d.min, d.max)
        # a deserialized sketch keeps working: add + merge
        back.add(0.5)
        assert back.count == d.count + 1

    def test_roundtrip_empty(self):
        back = QuantileDigest.from_dict(QuantileDigest().to_dict())
        assert back.count == 0 and math.isnan(back.quantile(0.5))

    def test_wrong_kind_refused(self):
        with pytest.raises(ValueError, match="kind"):
            QuantileDigest.from_dict({"kind": "histogram", "count": 0})

    def test_summary_ms_convention(self):
        d = QuantileDigest()
        d.add_many(np.full(1000, 0.125))  # 125ms
        s = d.summary_ms()
        assert set(s) == {"p50_ms", "p90_ms", "p99_ms", "count"}
        assert s["count"] == 1000
        assert s["p50_ms"] == pytest.approx(125.0, rel=0.01)
        assert QuantileDigest().summary_ms() == {
            "p50_ms": None, "p90_ms": None, "p99_ms": None, "count": 0}
        # p99 and p99.9 are distinct keys (int() formatting would
        # silently collide them)
        s = d.summary_ms(qs=(0.99, 0.999))
        assert set(s) == {"p99_ms", "p99.9_ms", "count"}


class TestValidation:
    def test_bad_ctor_args(self):
        with pytest.raises(ValueError):
            QuantileDigest(rel_err=0.0)
        with pytest.raises(ValueError):
            QuantileDigest(rel_err=1.0)
        with pytest.raises(ValueError):
            QuantileDigest(max_bins=4)

    def test_bad_quantile(self):
        d = QuantileDigest()
        d.add(1.0)
        with pytest.raises(ValueError):
            d.quantile(1.5)
