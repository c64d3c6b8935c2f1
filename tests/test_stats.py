"""strom/utils/stats exposition layer (ISSUE 3 satellites): exact _sum
through snapshots, counter-vs-gauge typing, HELP lines, non-dict section
tolerance, delta percentiles, and the bench-key parity contract with
tools/compare_rounds.py (silent renames must fail a test, not a dashboard)."""

import importlib.util
import os

import pytest

from strom.utils.stats import (StatsRegistry, all_counter_names, global_stats,
                               percentile_from_buckets, sections_prometheus)


def _load_compare_rounds():
    spec = importlib.util.spec_from_file_location(
        "compare_rounds",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools", "compare_rounds.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestExposition:
    def test_snapshot_carries_exact_total_us(self):
        reg = StatsRegistry("t")
        # values whose mean*count reconstruction would lose precision once
        # rounded: the snapshot must carry the exact accumulated sum
        for v in (3.1, 100.7, 0.9, 12345.678):
            reg.observe_us("lat", v)
        snap = reg.snapshot()
        assert snap["lat_total_us"] == pytest.approx(3.1 + 100.7 + 0.9
                                                     + 12345.678)
        # the Prometheus _sum is that exact total, not mean*count
        txt = reg.prometheus()
        sum_line = [l for l in txt.splitlines()
                    if l.startswith("t_lat_us_sum")][0]
        assert float(sum_line.split()[1]) == pytest.approx(snap["lat_total_us"])

    def test_counter_vs_gauge_typing_and_help(self):
        reg = StatsRegistry("t2")
        reg.add("bytes_read", 10)
        reg.set_gauge("depth", 4)
        txt = reg.prometheus()
        assert "# TYPE t2_bytes_read counter" in txt
        assert "# TYPE t2_depth gauge" in txt
        assert "# HELP t2_bytes_read" in txt
        assert "# HELP t2_depth" in txt

    def test_hist_summary_keys_not_duplicated_as_gauges(self):
        """The snapshot's derived p50/mean/count/total keys fold into the
        histogram block instead of doubling as free-standing gauges."""
        reg = StatsRegistry("t3")
        reg.observe_us("lat", 50.0)
        txt = reg.prometheus()
        assert "# TYPE t3_lat_us histogram" in txt
        for stray in ("t3_lat_p50_us", "t3_lat_mean_us", "t3_lat_total_us",
                      "t3_lat_count "):
            assert stray not in txt

    def test_sections_prometheus_skips_non_dict_sections(self):
        txt = sections_prometheus({
            "ok": {"n": 1, "flag": True, "name": "python", "frac": 0.5},
            "weird": "just a string",
            "also_weird": 42,
            "none_section": None,
        })
        assert "strom_ok_n 1" in txt
        assert "strom_ok_flag 1" in txt       # bool -> 0/1 gauge
        assert "strom_ok_frac 0.5" in txt
        assert "python" not in txt            # string leaf skipped
        assert "weird" not in txt             # non-dict sections skipped

    def test_sections_counter_typing_via_registry_mirror(self):
        """Section keys that mirror a registered monotonic counter type as
        counter; unknown keys stay gauges."""
        global_stats.add("parity_mirror_total", 2)
        txt = sections_prometheus({"s": {"parity_mirror_total": 2,
                                         "some_gauge": 1}})
        assert "# TYPE strom_s_parity_mirror_total counter" in txt
        assert "# TYPE strom_s_some_gauge gauge" in txt
        assert "parity_mirror_total" in all_counter_names()

    def test_percentile_from_buckets_on_deltas(self):
        reg = StatsRegistry("t4")
        for _ in range(5):
            reg.observe_us("lat", 10.0)
        snap0 = reg.snapshot()
        for _ in range(4):
            reg.observe_us("lat", 1000.0)
        snap1 = reg.snapshot()
        delta = [a - b for a, b in zip(snap1["lat_hist"], snap0["lat_hist"])]
        # the DELTA window contains only the 1000us observations: its p50 is
        # the 1000us bucket's upper bound, while the cumulative hist's p50
        # would still straddle the early 10us points
        assert percentile_from_buckets(delta, 0.50) == 1024.0
        assert percentile_from_buckets(snap1["lat_hist"], 0.50) < 1024.0
        assert percentile_from_buckets([], 0.5) == 0.0
        assert percentile_from_buckets([0, 0, 0], 0.9) == 0.0

    def test_hist_lines_fallback_without_total(self):
        """Producers that hand-assemble stats dicts (engine aggregations
        predating the exact-sum key) still expose a histogram: _sum falls
        back to mean*count."""
        txt = sections_prometheus({"e": {
            "read_latency_hist": [0, 2, 0], "read_latency_mean_us": 3.0,
            "read_latency_count": 2}})
        assert 'e_read_latency_us_bucket{le="+Inf"} 2' in txt
        assert "e_read_latency_us_sum 6.0" in txt


class TestBenchKeyParity:
    """Every stats key tools/compare_rounds.py consumes must be one a bench
    artifact actually produces — a rename on either side fails HERE instead
    of silently blanking a dashboard column (ISSUE 3 satellite)."""

    def test_stall_keys_match_producers(self):
        from strom.obs.stall import STALL_FIELDS

        cr = _load_compare_rounds()
        produced = set(STALL_FIELDS)
        prefixes = ("train", "resnet_predecoded", "vit_predecoded",
                    "resnet", "vit")
        for key in cr.STALL_KEYS:
            suffix = next((key[len(p) + 1:] for p in prefixes
                           if key.startswith(p + "_")), None)
            assert suffix is not None, key
            assert suffix in produced, \
                f"compare_rounds consumes {key!r} but stall attribution " \
                f"produces no {suffix!r} (renamed bucket?)"

    def test_stall_fields_round_trip_through_flatten(self):
        from strom.obs import stall

        flat = stall.flatten_summary(stall.steps_summary([]))
        assert set(flat) == set(stall.STALL_FIELDS)
