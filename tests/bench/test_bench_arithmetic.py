"""The benchmark's own arithmetic, on the CPU and without a chip.

Lookups by name, the traffic schedule, percentiles, the scan work, the
peak table, the trace reduction and the plain reference.
"""

from __future__ import annotations

import gzip
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import gen, harness, pins, spec, trace, work  # noqa: E402
from bench.reference import (  # noqa: E402
    Answers, compare, group_codes, reference_answers)
from bench.serve import _definition  # noqa: E402


# ----------------------------------------------------------------- lookups

@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    c = spec.load_cell(cell)
    assert c.config["name"] == cell.split(".")[0]
    assert c.traffic["rate_qps"] > 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.metric_reader(m["name"]))


def test_unknown_names_are_refused():
    bench = spec.load_benchmark()
    with pytest.raises(KeyError):
        spec.load_cell("no_such_cell", bench)
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric")
    broken = json.loads(json.dumps(bench))
    broken["workloads"][0]["traffic"] = "no_such_traffic"
    with pytest.raises(KeyError):
        spec.load_cell(broken["workloads"][0]["name"], broken)
    broken = json.loads(json.dumps(bench))
    cell = broken["workloads"][0]["name"]
    broken["per_layer"].append({"name": "no_reader", "unit": "s",
                                "moves": "setup_s", "workloads": [cell]})
    with pytest.raises(KeyError):
        spec.load_cell(cell, broken)


def test_a_per_layer_metric_must_list_its_cells():
    bench = spec.load_benchmark()
    cell = bench["workloads"][0]["name"]
    assert spec.load_cell(cell, bench).per_layer
    broken = json.loads(json.dumps(bench))
    del broken["per_layer"][0]["workloads"]
    with pytest.raises(KeyError):
        spec.load_cell(cell, broken)
    # a variant has a reader of its own; none is shared by prefix
    with pytest.raises(KeyError):
        spec.metric_reader("launch_ms.overload")


def test_the_compilation_cache_lives_in_a_directory_made_in_the_checkout(
        monkeypatch, tmp_path):
    import jax

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    try:
        path = spec.use_compilation_cache()
        assert path == tmp_path / ".jax_cache" and path.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])


def test_a_device_missing_from_the_peak_table_raises():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.load_peaks("cpu")


# ----------------------------------------------------------------- traffic

def _schedule(seed, rate=40.0, seconds=5.0, n_weights=8):
    data = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
    return gen.make_schedule({"rate_qps": rate, "zipf_s": 0.99,
                              "q_noise": 3.0}, seconds, data, n_weights,
                             gen.seeds(seed)[2])


def test_a_seed_gives_the_same_schedule():
    a, b = _schedule(2**31 + 5), _schedule(2**31 + 5)
    for x, y in ((a.due_s, b.due_s), (a.weight_ids, b.weight_ids),
                 (a.queries, b.queries)):
        np.testing.assert_array_equal(x, y)


def test_seeds_reorder_the_same_work():
    a, b = _schedule(1), _schedule(2)
    assert len(a) == len(b) == 200
    assert not np.array_equal(a.weight_ids, b.weight_ids)
    np.testing.assert_array_equal(np.sort(a.weight_ids),
                                  np.sort(b.weight_ids))
    # the gaps are one fixed set of exponential quantiles in another order,
    # stretched to fill the window; the last runs to the window's close
    u = (np.arange(200) + 0.5) / 200
    want = -np.log1p(-u)
    want *= 5.0 / want.sum()
    for s in (a, b):
        gaps = np.append(np.diff(s.due_s), 5.0 - s.due_s[-1])
        np.testing.assert_allclose(np.sort(gaps), want, rtol=1e-9)
    for s in (a, b):
        assert s.due_s[0] == 0.0 and s.due_s[-1] < 5.0
        assert np.all(np.diff(s.due_s) > 0)


def test_zipf_counts_rank_weight_ids_by_popularity():
    c = gen.zipf_counts(1000, 8, 0.99)
    assert c.sum() == 1000
    assert np.all(np.diff(c) <= 0) and c[0] > 2 * c[7]
    p = 1 / np.arange(1, 9) ** 0.99
    np.testing.assert_allclose(c, 1000 * p / p.sum(), atol=1.0)


# ------------------------------------------------------------- percentiles

def test_percentiles_come_from_raw_samples():
    v = np.arange(1, 101, dtype=np.float64)
    assert harness.percentile(v, 50) == 50.5
    assert harness.percentile(v, 95) == pytest.approx(95.05)
    assert harness.percentile([1.0, np.nan, 3.0], 100) == np.inf
    assert harness.percentile([1.0, np.nan, 3.0], 25) == 2.0
    assert harness.percentile([1.0, np.nan, 3.0], 75) == np.inf


# -------------------------------------------------------------- scan work

def test_scan_bytes_match_shapes_worked_by_hand():
    # 200,000 rows; widest member 474 tables of int32 codes; 128 float32
    assert work.scan_bytes(200_000, 474, 128) == 200_000 * (1896 + 512)
    assert work.scan_bytes(100_000, 392, 960) == 100_000 * (1568 + 3840)
    # 8 queries x 2 passes x rows x ((16 + 1) levels x 3 x 480 + 3 x 128)
    assert work.scan_ops(8, 1000, 480, 16, 128) == 2 * 8 * 1000 * (
        17 * 3 * 480 + 384)


# -------------------------------------------------------- trace reduction

def _synthetic():
    ms = 1_000_000
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 0 * ms, 10 * ms],  # clipped to [5, 10)
            ["fusion.2", 20 * ms, 10 * ms],
            ["fusion.3", 25 * ms, 10 * ms],  # overlaps .2: union [20, 35)
            ["copy", 60 * ms, 50 * ms],  # clipped to [60, 105)
        ]},
        "host": [
            ["bench_trace_window", 5 * ms, 100 * ms],  # [5, 105)
            ["bench_driver_tick", 10 * ms, 60 * ms],  # [10, 70)
            ["wlsh_query_step[(1, 2)]", 12 * ms, 5 * ms],  # [12, 17)
            ["bench_submit", 40 * ms, 10 * ms],  # [40, 50)
        ],
    }


def test_trace_reduction_gives_known_answers():
    r = trace.reduce_events(_synthetic())
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    # busy [5,10) + [20,35) + [60,105) = 65 ms
    assert r["busy_s"] == pytest.approx(0.065)
    assert r["idle_share"] == pytest.approx(0.35)
    # gaps [10,20) mid 15 -> query step; [35,60) mid 47.5 -> submit
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"wlsh_query_step": 0.010, "bench_submit": 0.025})
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.005, "fusion.2": 0.010, "fusion.3": 0.010,
         "copy": 0.045})


def test_trace_reduction_needs_its_window():
    ev = _synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        trace.reduce_events(ev)


# --------------------------------------------------------------- reference

TINY_CFG = dict(n=1500, d=12, value_range=10_000, p=2.0, c=3, k=5,
                gamma_n=100.0, tau=500.0, v=4, n_weights=8, n_subset=2,
                n_subrange=10, weights_seed=1)


@pytest.fixture(scope="module")
def tiny_index():
    """A tiny planned index, the program's own host oracle beside it."""
    from repro.core.params import PlanConfig
    from repro.core.wlsh import WLSHIndex

    cfg = dict(TINY_CFG, plan=pins.plan_pins(TINY_CFG))
    data = gen.make_dataset(cfg["n"], cfg["d"], cfg["value_range"],
                            gen.seeds(3)[0])
    weights = gen.make_weight_set(8, cfg["d"], 2, 10, seed=1)
    host = WLSHIndex(data, weights, PlanConfig(p=2.0, c=3, n=cfg["n"],
                                               gamma_n=100.0),
                     tau=500.0, v=4, v_prime=4, seed=5)
    plan = host.export_serving_plan()
    return data, weights, host, plan, _definition(cfg, weights, plan), cfg


def test_pinned_plan_equals_the_planners_export(tiny_index):
    _, _, _, plan, _, cfg = tiny_index
    assert pins.plan_mismatches(cfg["plan"], plan) == 0
    moved = json.loads(json.dumps(cfg["plan"]))
    moved["members"][3]["mu"] += 1
    moved["members"][5]["beta"] -= 1
    moved["groups"][0]["width"] *= 2
    assert pins.plan_mismatches(moved, plan) == 3
    moved["groups"].pop()
    assert pins.plan_mismatches(moved, plan) >= 4


def test_reference_codes_equal_the_planners(tiny_index):
    data, _, _, plan, defn, _ = tiny_index
    for g, fam in zip(plan.groups, defn.families):
        np.testing.assert_array_equal(group_codes(data, fam), g.codes)


def test_reference_agrees_with_the_programs_host_oracle(tiny_index):
    data, weights, host, _, defn, _ = tiny_index
    rng = np.random.default_rng(0)
    wids = rng.integers(0, 8, size=24)
    qs = (data[rng.integers(0, len(data), 24)]
          + rng.normal(0, 3, (24, data.shape[1]))).astype(np.float32)
    ref, (own,) = reference_answers(data, weights, qs, wids, defn, k=5,
                                    check_ids=[np.full((24, 5), -1)])
    assert np.all(np.isinf(own))
    for i in range(24):
        want = host.search_dense(qs[i], weight_id=int(wids[i]), k=5)
        np.testing.assert_array_equal(ref.ids[i], want.ids)
        assert ref.stop[i] == want.stats.stop_level
        assert ref.n_checked[i] == want.stats.n_checked
        np.testing.assert_allclose(ref.dists[i], want.dists, rtol=1e-12)


def test_compare_counts_mismatches_and_distance_gaps():
    ids = np.array([[1, 2], [3, 4]])
    d = np.array([[1.0, 2.0], [3.0, np.inf]])
    ref = Answers(ids, d, np.array([2, 3]), np.array([5, 5]))
    same = Answers(ids.copy(), d.copy(), ref.stop.copy(),
                   ref.n_checked.copy())
    assert compare(same, ref, d, 1e-6) == {"mismatch_share": 0.0,
                                           "dist_rel_err_max": 0.0}
    off = Answers(ids.copy(), d * 1.001, np.array([2, 4]), ref.n_checked)
    got = compare(off, ref, d, 1e-6)
    assert got["mismatch_share"] == 0.5
    assert got["dist_rel_err_max"] == pytest.approx(1e-3)


def test_compare_takes_a_swap_only_of_rows_tied_within_rounding():
    ref = Answers(np.array([[1, 2, 3]]), np.array([[1.0, 2.0, 2.0 + 1e-9]]),
                  np.array([2]), np.array([9]))

    def share(ids, served_ref_dists):
        served = Answers(np.array([ids]), ref.dists.copy(), ref.stop,
                         ref.n_checked)
        return compare(served, ref, np.array([served_ref_dists]),
                       1e-6)["mismatch_share"]

    # ranks 2 and 3 swapped: their float64 distances tie within 1e-6
    assert share([1, 3, 2], [1.0, 2.0 + 1e-9, 2.0]) == 0.0
    # a tied row the reference left out (another row at the same distance)
    assert share([1, 2, 7], [1.0, 2.0, 2.0 + 2e-9]) == 0.0
    # ranks 1 and 2 swapped: 1.0 and 2.0 are no tie
    assert share([2, 1, 3], [2.0, 1.0, 2.0 + 1e-9]) == 1.0
    # a row that is no candidate at the stop level (inf) never matches
    assert share([1, 2, 7], [1.0, 2.0, np.inf]) == 1.0
    # a repeated row never matches, however close
    assert share([1, 2, 2], [1.0, 2.0, 2.0]) == 1.0
    # a row where the reference has none is a mismatch
    hole = Answers(ref.ids.copy(), ref.dists.copy(), ref.stop, ref.n_checked)
    hole.ids[0, 2], hole.dists[0, 2] = -1, np.inf
    served = Answers(np.array([[1, 2, 3]]), ref.dists.copy(), ref.stop,
                     ref.n_checked)
    assert compare(served, hole, ref.dists.copy(),
                   1e-6)["mismatch_share"] == 1.0


def test_recorded_chip_trace_reduces_to_its_known_answers():
    path = ROOT / "bench" / "testdata" / "v5e_trace_events.json.gz"
    with gzip.open(path, "rt") as fh:
        rec = json.load(fh)
    r = trace.reduce_events(rec["events"])
    want = rec["reduced"]
    assert r["n_devices"] == 1
    for key in ("window_s", "busy_s", "idle_share"):
        assert r[key] == pytest.approx(want[key], rel=1e-12)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert [n for n, _ in r["device_ops"]] == [
        n for n, _ in want["device_ops"]]
    assert dict(r["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]))
    # every idle nanosecond is attributed to exactly one host activity
    assert len(r["idle_gaps"]) < 10
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
