"""The yardstick's arithmetic on known inputs: percentiles, spreads,
interval unions, operation counts, rooflines, the peaks table, the row
comparison, and the traffic generator's guarantees. No device."""

import numpy as np
import pytest

from benchmark.harness import check, flops, peaks, stats, traffic, xplane

FLAGSHIP = {"vocab_size": 60000, "emb_sz": 800, "n_hid": 2500,
            "n_layers": 4, "qrnn": False}


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 3.0), (95, 5.0),
                                    (100, 5.0)])
def test_percentile_nearest_rank(q, want):
    assert stats.percentile([5.0, 1.0, 3.0, 2.0, 4.0], q) == want


def test_iqr_share_is_pythons_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles(n=4): q1 = 10.75, q3 = 14.25, median 12.5
    assert stats.iqr_share(vals) == pytest.approx(3.5 / 12.5)


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6)]
    assert stats.union_seconds(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]


def test_flagship_operation_counts():
    # 4H(in+H): 33.0M + 50M + 50M + 10.56M weights in the recurrence
    assert flops.encoder_matmul_params(FLAGSHIP) == 143_560_000
    assert flops.encoder_flops_per_token(FLAGSHIP) == 2 * 143_560_000
    fwd = 2 * 143_560_000 + 2 * 800 * 60000
    assert flops.lm_forward_flops_per_token(FLAGSHIP) == fwd
    assert flops.lm_train_flops_per_token(FLAGSHIP) == 3 * fwd  # 1.149e9
    q = dict(FLAGSHIP, qrnn=True)
    assert flops.encoder_matmul_params(q) == (
        7500 * 1600 + 2 * 7500 * 2500 + 2400 * 2500)


def test_roofline_says_which_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    t, bound = flops.roofline_seconds(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9 * 2, pk)
    assert (t, bound) == (2.0, "memory")


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_row_numbers_and_judge():
    rng = np.random.default_rng(0)
    want = rng.normal(size=(4, 9))
    got = want.copy()
    got[:, :3] *= 1.01  # the mean third 1 % off
    nums = check.row_numbers(got, want)
    assert nums["rel_rms_mean"] == pytest.approx(0.01)
    assert nums["rel_rms_max"] == 0.0 and nums["nonfinite"] == 0.0
    ok = check.judge(nums, {"rel_rms_mean": 0.02, "rel_rms_max": 0.0})
    bad = check.judge(nums, {"rel_rms_mean": 0.005})
    assert ok["correct"] and not bad["correct"]
    got[0, 0] = np.nan
    assert check.row_numbers(got, want)["nonfinite"] == 1.0
    # a limit without its number is a failure, not a pass
    assert not check.judge({}, {"rel_rms_mean": 1.0})["correct"]


def test_name_gaps_innermost_span_wins():
    gaps = [(0.0, 10.0)]
    spans = [("outer", 0.0, 10.0), ("inner", 2.0, 5.0)]
    named = dict(map(tuple, xplane.name_gaps(gaps, spans)))
    assert named == {"outer": pytest.approx(7.0), "inner": pytest.approx(3.0)}
    named = dict(map(tuple, xplane.name_gaps([(0.0, 4.0)],
                                             [("a", 1.0, 2.0)])))
    assert named["(no span)"] == pytest.approx(3.0)


def test_reduce_events_on_known_intervals():
    E = xplane.Event
    dev = "/device:TPU:0"
    events = [E(dev, xplane.MODULE_LINE, "jit_fwd(1)", 0.0, 2.0),
              E(dev, xplane.MODULE_LINE, "jit_fwd(1)", 3.0, 1.0),
              E(dev, xplane.OP_LINE, "%fusion.1 = bf16[2]{0} fusion()", 0.0, 1.5),
              E(dev, xplane.OP_LINE, "%copy.2 = f32[4]{0} copy()", 3.0, 1.0),
              E("/host:CPU", "main", "bench.call", 0.0, 4.0)]
    r = xplane.reduce_events(events)
    assert r["busy_s"] == pytest.approx(2.5)
    assert r["window_s"] == pytest.approx(4.0)
    assert r["modules"] == {"jit_fwd": [2.0, 1.0]}
    assert r["device_ops"][0] == ["fusion.1 bf16[2]", 1.5]
    assert r["gaps"] == [(1.5, 3.0)]
    assert xplane.reduce_events([])["devices"] == 0


MIX = {"docs_per_call": 50, "length": {"dist": "lognormal", "median": 120,
                                       "sigma": 1.0, "min": 8, "max": 2048}}


def test_length_grid_is_fixed_and_clipped():
    grid = traffic.length_grid(MIX["length"], 1000)
    assert grid.min() == 8 and grid.max() == 2048
    assert np.median(grid) == pytest.approx(120, abs=1)
    assert 185 < grid.mean() < 205


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_every_seed_does_the_same_work(seed):
    words = traffic.vocab_words(["xxunk", "xxpad", "xxbos", "xxxfldtitle",
                                 "xxxfldbody", "xxlst", "xxcdi", "xxlnk",
                                 "xxcdb"], 700)
    a = traffic.make_document_calls(MIX, words, seed, 2)
    b = traffic.make_document_calls(MIX, words, seed, 2)
    ref = traffic.make_document_calls(MIX, words, 1, 1)
    lens = lambda call: sorted(len(d["ids"]) for d in call)  # noqa: E731
    assert lens(a[0]) == lens(a[1]) == lens(ref[0])
    assert [d["body"] for d in a[0]] == [d["body"] for d in b[0]]
    assert [d["body"] for d in a[0]] != [d["body"] for d in ref[0]]


def test_token_stream_targets_are_inputs_shifted_by_one():
    s = traffic.make_token_stream({}, 500, 19, 2, 2**31 + 5, rows=3, bptt=5,
                                  windows=4)
    assert s["x"].shape == s["y"].shape == (4, 3, 5)
    assert (s["x"][0, :, 1:] == s["y"][0, :, :-1]).all()
    assert (s["x"][1, :, 0] == s["y"][0, :, -1]).all()
    assert s["x"].min() >= 2 and s["x"].max() < 500


def test_vocab_words_are_unique_lowercase_and_sized():
    words = traffic.vocab_words(["xxunk", "xxpad"], 60000)
    assert len(words) == len(set(words)) == 60000
    assert all(w.isalpha() and w.islower() for w in words[5:])
