"""CPU tests of the ``dbrx_132b.ep4_batch`` cell's files.

  JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

The cell finds its configuration, mix and readers by name in the real
``BENCHMARK.json``; its configuration file states what the program
runs; and the reader of the decode program's collective time reads a
small trace written by hand.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

import cellspec  # noqa: E402
import counts  # noqa: E402
import xplane  # noqa: E402

CELL = "dbrx_132b.ep4_batch"
DEV = ("/device:TPU:0", "/device:TPU:1", "/device:TPU:2", "/device:TPU:3")
HOST = "/host:CPU"


def _conf(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def test_cell_resolves_on_four_chips():
    cell = cellspec.Cell(ROOT, CELL)
    assert cell.chips == 4 and cell.conf["chips"] == 4
    assert cell.entry["traffic"] == "ep4_batch"
    assert cell.mix == {"loop": "closed", "clients": 8, "prompt_len": 1024,
                        "max_news": [64], "max_new_probs": [1.0],
                        "capacity": 8}
    assert {m["name"] for m in cell.end_to_end} == {
        "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == [
        "collective_ms_per_prefill", "collective_ms_per_decode",
        "prefill_device_ms_per_ktok.ep4", "decode_device_ms.ep4",
        "decode_hbm_roofline.ep4", "mfu.ep4", "idle_share.ep4",
        "sched_host_ms_per_iter.ep4", "mfu.decode.ep4"]


@pytest.mark.parametrize("metric, file", [
    ("prefill_device_ms_per_ktok.ep4", "prefill_device_ms_per_ktok"),
    ("decode_device_ms.ep4", "decode_device_ms"),
    ("decode_hbm_roofline.ep4", "decode_hbm_roofline"),
    ("mfu.ep4", "mfu"), ("idle_share.ep4", "idle_share"),
    ("sched_host_ms_per_iter.ep4", "sched_host_ms_per_iter"),
    ("mfu.decode.ep4", "mfu.decode"),
])
def test_suffixed_entries_read_the_existing_readers(metric, file):
    """Each ``.ep4`` entry is read by the reader the chat cell's entry
    of the same quantity uses, found by the dotted-name fallback."""
    path = os.path.join(CHIP, "metrics", file + ".py")
    read = cellspec.reader(ROOT, metric)
    assert read.__code__.co_filename == path


def test_configuration_is_the_unread_file_with_limits():
    """The cell's file is ``dbrx_132b.json`` (published widths, 5 of 40
    layers, the same deployment) with the limits read on the chip."""
    new, old = _conf("dbrx_132b_ep4"), _conf("dbrx_132b")
    assert {k: v for k, v in new.items() if not k.startswith("check")} == \
        {k: v for k, v in old.items() if not k.startswith("check")}
    assert set(new["check"]) == {"mean_gap", "mismatch_share"}
    assert all(isinstance(v, (int, float)) and v > 0
               for v in new["check"].values())


def test_program_config_matches_the_file():
    conf = _conf("dbrx_132b_ep4")
    cfg = cellspec.program_config(conf)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (5, 6144, 48, 8, 128)
    assert (cfg.num_experts, cfg.top_k, cfg.expert_d_ff, cfg.vocab) == \
        (16, 4, 10752, 100352)
    assert cfg.moe_capacity == 4.0 and not cfg.tie_embeddings
    assert cfg.rope_theta == 5e5


def test_counts_match_the_program():
    from repro.models.api import param_count_shape_only
    conf = _conf("dbrx_132b_ep4")
    shape = counts.shape_of(conf)
    assert shape.chips == 4 and shape.experts == 16 and shape.top_k == 4
    assert counts.param_count(shape) == \
        param_count_shape_only(cellspec.program_config(conf))


def _ev(plane, line, name, start_us, dur_us):
    return xplane.Event(plane, line, name, start_us * 1e3, dur_us * 1e3)


def _trace(decodes: int):
    """A 1 ms slice on four chips: one prefill with an all-to-all, then
    ``decodes`` decode runs of 100 us, each with a 20 us all-to-all and
    a 10 us all-reduce."""
    rows = [_ev(HOST, "python3", "bench.slice", 0, 1000)]
    for plane in DEV:
        rows += [_ev(plane, "XLA Modules", "jit_prefill(3)", 0, 200),
                 _ev(plane, "XLA Ops", "fusion.1", 0, 150),
                 _ev(plane, "XLA Ops", "all-to-all.2", 150, 50)]
        for i in range(decodes):
            t = 300 + 150 * i
            rows += [_ev(plane, "XLA Modules", "jit_decode(4)", t, 100),
                     _ev(plane, "XLA Ops", "fusion.5", t, 70),
                     _ev(plane, "XLA Ops", "%all-to-all.6 = bf16[2,8]{1,0} "
                         "all-to-all(bf16[2,8]{1,0} %fusion.5)", t + 70, 20),
                     _ev(plane, "XLA Ops", "all-reduce.7", t + 90, 10)]
    return rows


@pytest.mark.parametrize("decodes", [1, 3])
def test_collective_ms_per_decode_reads_a_small_trace(decodes):
    run = type("Run", (), {"reduction": xplane.reduce(_trace(decodes))})
    read = cellspec.reader(ROOT, "collective_ms_per_decode")
    assert read(run) == pytest.approx(0.030)
    assert cellspec.reader(ROOT, "collective_ms_per_prefill")(run) == \
        pytest.approx(0.050)


def test_collective_ms_per_decode_is_silent_without_a_decode():
    run = type("Run", (), {"reduction": xplane.reduce(_trace(0))})
    assert cellspec.reader(ROOT, "collective_ms_per_decode")(run) is None
