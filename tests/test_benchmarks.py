"""Tests for the paper-figure tables + roofline builder."""

import json
import os

import pytest

from benchmarks import paper_figures, roofline


class TestPaperFigures:
    def test_all_figures_produce_rows(self):
        for name, fn in paper_figures.ALL.items():
            rows = fn()
            assert rows, name

    def test_fig6_reduction_matches_paper(self):
        rows = paper_figures.fig6_allgather()
        mw = [r for r in rows if r["scheme"] == "multiwrite_paired"][0]
        assert abs(mw["reduction_pct"] - 30.0) < 3.0

    def test_table1_errors_within_tolerance(self):
        for r in paper_figures.table1_cross():
            assert abs(r["w_err_pct"]) < 12
            assert abs(r["wo_err_pct"]) < 8


class TestRoofline:
    def test_load_and_markdown(self, tmp_path, monkeypatch):
        fake = {
            "arch": "x", "shape": "train_4k", "mesh": "single",
            "variant": "mw", "chips": 256, "kind": "train",
            "cost": {"flops_per_device": 1e12, "bytes_per_device": 1e11},
            "roofline": {"compute_term_s": 1e12 / 197e12,
                         "memory_term_s": 1e11 / 819e9,
                         "collective_term_s": 0.001,
                         "dominant": "memory",
                         "model_flops_global": 5e13,
                         "useful_flops_ratio": 0.5},
            "memory": {}, "collectives": {"by_axis": {}},
        }
        d = tmp_path / "dryrun"
        d.mkdir()
        with open(d / "x__train_4k__single__mw.json", "w") as f:
            json.dump(fake, f)
        monkeypatch.setattr(roofline, "RESULTS", str(d))
        monkeypatch.setattr(roofline, "model_flops",
                            lambda a, s: 5e13)
        rows = roofline.load()
        assert len(rows) == 1
        md = roofline.markdown(rows)
        assert "train_4k" in md and "memory" in md

    def test_real_results_if_present(self):
        rows = roofline.load()
        if not rows:
            pytest.skip("no dry-run results present")
        ok = [r for r in rows if "error" not in r and "skipped" not in r]
        assert ok, "all cells errored"
        # every runnable cell has the three terms
        for r in ok:
            rl = r["roofline"]
            assert rl["compute_term_s"] >= 0
            assert rl["memory_term_s"] > 0
            assert rl["dominant"] in ("compute", "memory", "collective")

    def test_skip_records_present_for_full_attention_archs(self):
        rows = roofline.load()
        if not rows:
            pytest.skip("no dry-run results present")
        skipped = [r for r in rows if "skipped" in r]
        if skipped:
            assert all(r["shape"] == "long_500k" for r in skipped)
