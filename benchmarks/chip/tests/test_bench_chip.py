"""CPU tests of the chip benchmark's harness.

  JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

The trace reduction on a small trace written by hand and every
per-layer reader on it, that each cell finds its files by name, the
percentile and TPOT arithmetic, the traffic generator as a function of
the seed, the FLOP and byte counts against the configurations'
parameter counts, and tiny end-to-end runs: the command refuses the CPU, and a run driven
past that refusal is correct on the program and not correct with each
fault the cells can have (a served token altered; a decode step that
leaves the cohort's cache unchanged; half of a cohort's rows computed
and their results given to the rest; the exchange between chips left
out) and with the float8 control in the program's place.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(ROOT, "src"))

import counts  # noqa: E402
import seedtraffic  # noqa: E402
import serveloop  # noqa: E402
import xplane  # noqa: E402

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"


def ev(plane, line, name, start_us, dur_us, module=""):
    return xplane.Event(plane, line, name, start_us * 1e3, dur_us * 1e3,
                        module)


def small_trace():
    """A 1 ms slice on two chips: a prefill (a while loop holding a
    fusion, then an all-to-all), a decode, and host spans around them."""
    rows = [ev(HOST, "python3", "bench.slice", 0, 1000),
            ev(HOST, "python3", "sched.step", 0, 700),
            ev(HOST, "python3", "engine.start_cohort", 10, 400),
            ev(HOST, "python3", "engine.step_cohort", 500, 200),
            ev(HOST, "python3", "harness.stamp", 920, 50)]
    for plane in (DEV0, DEV1):
        rows += [ev(plane, "XLA Modules", "jit_prefill(7)", 100, 300),
                 ev(plane, "XLA Ops", "%while.5 = (s32[], bf16[4,8]) "
                    "while(%tuple.2), body=%region_0", 100, 200),
                 ev(plane, "XLA Ops", "%fusion.1 = bf16[4,8]{1,0} fusion("
                    "bf16[4,8]{1,0} %all-reduce.9), kind=kLoop", 100, 150),
                 ev(plane, "XLA Ops", "%all_to_all.3 = bf16[4,8]{1,0} "
                    "all-to-all(bf16[4,8]{1,0} %fusion.1)", 300, 100),
                 ev(plane, "XLA Modules", "jit_decode(9)", 600, 100),
                 ev(plane, "XLA Ops", "fusion.2", 600, 60,
                    module="jit_decode"),
                 ev(plane, "XLA Ops", "all-reduce-start.1", 650, 50)]
    return rows


def test_trace_reduction():
    red = xplane.reduce(small_trace())
    assert red.devices == 2
    assert red.window_s == pytest.approx(1e-3)
    # the loop holds the fusion, and the all-reduce starts at 650 us
    # inside the decode's fusion: the union is 300 + 100 us per chip, the
    # overlap is the later op's, and nothing counts twice
    assert red.busy_s == pytest.approx(400e-6)
    assert red.idle_share == pytest.approx(0.6)
    assert red.module_s["jit_prefill"] == pytest.approx(300e-6)
    assert red.module_s["jit_decode"] == pytest.approx(100e-6)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s)
    assert red.module_runs == {"jit_prefill": 1.0, "jit_decode": 1.0}
    assert red.collective_s["jit_prefill"] == pytest.approx(100e-6)
    assert red.collective_s["jit_decode"] == pytest.approx(50e-6)
    # chip 0 is idle 0-100 (middle in start_cohort, the innermost span
    # there), 400-600 (middle in step_cohort) and 700-1000 (middle in no
    # span)
    assert red.gap_s == {"engine.start_cohort": pytest.approx(100e-6),
                         "engine.step_cohort": pytest.approx(200e-6),
                         "host.unmarked": pytest.approx(300e-6)}
    top = red.breakdown()
    assert top["device_ops"][:2] == [
        ["jit_prefill/fusion.1", pytest.approx(150e-6)],
        ["jit_prefill/all_to_all.3", pytest.approx(100e-6)]]
    assert red.op_s["jit_decode/fusion.2"] == pytest.approx(50e-6)
    assert red.op_s["jit_prefill/while.5"] == pytest.approx(50e-6)
    assert [k for k, _ in top["idle_gaps"]] == [
        "host.unmarked", "engine.step_cohort", "engine.start_cohort"]


def test_recorded_trace():
    """57 ms of a traced chat run on one v5e (device ops of one prefill
    and four decodes, each op named by the start of its HLO text): the
    ops' own times add up to the programs' spans, though the prefill's
    layer loop holds its body's ops, and no op is a collective."""
    with open(os.path.join(HERE, "recorded_chat_trace.json")) as f:
        rec = json.load(f)
    w0, w1 = rec["slice_ns"]
    rows = [xplane.Event(HOST, "main", "bench.slice", w0, w1 - w0)]
    rows += [xplane.Event(DEV0, line, name, start, dur)
             for line, name, start, dur in rec["device"]]
    red = xplane.reduce(rows)
    spans = {}
    for e in rows:
        if e.line == "XLA Modules" and w0 <= e.start_ns < w1:
            kind = xplane.module_kind(e.name)
            spans[kind] = spans.get(kind, 0.0) + (
                min(e.end_ns, w1) - max(e.start_ns, w0)) * 1e-9
    for kind in ("jit_prefill", "jit_decode"):
        assert red.module_s[kind] == pytest.approx(spans[kind], rel=0.01)
    assert red.module_runs["jit_prefill"] == 1
    assert red.module_runs["jit_decode"] == 4
    assert sum(red.op_s.values()) <= red.busy_s * (1 + 1e-9)
    assert 0 < red.busy_s < red.window_s
    assert red.collective_s == {}
    assert any(k.startswith("jit_prefill/while") for k in red.op_s)


def test_nested_op_ending_past_its_loop_counts_once():
    """An inner loop that ends with its outer loop, a hair later after
    picoseconds were rounded to float nanoseconds, is still nested."""
    rows = [ev(HOST, "python3", "bench.slice", 0, 1000),
            ev(DEV0, "XLA Modules", "jit_prefill(7)", 0, 900),
            ev(DEV0, "XLA Ops", "%while.54 = () while()", 0, 900),
            ev(DEV0, "XLA Ops", "%while.55 = () while()", 100,
               800 + 1e-9),
            ev(DEV0, "XLA Ops", "%all_to_all.2 = () all-to-all()", 200, 300)]
    red = xplane.reduce(rows)
    assert red.busy_s == pytest.approx(900e-6)
    assert sum(red.op_s.values()) == pytest.approx(red.busy_s)
    assert red.op_s["jit_prefill/while.54"] == pytest.approx(100e-6)
    assert red.op_s["jit_prefill/while.55"] == pytest.approx(500e-6)
    assert red.collective_s["jit_prefill"] == pytest.approx(300e-6)


def test_collective_classification():
    kinds = {n: xplane.collective_kind(n) for n in (
        "all-to-all.12", "collective-permute-start.2", "all-gather.1",
        "all-reduce-done.4", "reduce-scatter.9", "fusion.3", "copy.1",
        "%all_to_all.297 = bf16[2,8]{1,0} all-to-all(bf16[2,8]{1,0} %a)",
        "%fusion.4 = f32[8] fusion(f32[8] %all-to-all.2), kind=kLoop")}
    assert kinds == {"all-to-all.12": "all-to-all",
                     "collective-permute-start.2": "collective-permute",
                     "all-gather.1": "all-gather",
                     "all-reduce-done.4": "all-reduce",
                     "reduce-scatter.9": "reduce-scatter",
                     "fusion.3": None, "copy.1": None,
                     "%all_to_all.297 = bf16[2,8]{1,0} all-to-all("
                     "bf16[2,8]{1,0} %a)": "all-to-all",
                     "%fusion.4 = f32[8] fusion(f32[8] %all-to-all.2), "
                     "kind=kLoop": None}


def test_trace_without_slice_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce([r for r in small_trace() if r.name != "bench.slice"])


@pytest.mark.parametrize("q", [0, 25, 50, 95, 99, 100])
def test_percentile_matches_numpy(q):
    values = list(np.random.default_rng(3).exponential(size=37))
    assert serveloop.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_tpot():
    st = serveloop.Stamp(rid=0, prompt_len=8, max_new=5, due=0.0,
                         first=1.0, last=1.8, emitted=5)
    assert serveloop.tpot_s(st) == pytest.approx(0.2)
    st.emitted = 1
    assert serveloop.tpot_s(st) is None
    assert np.isnan(serveloop.percentile([], 95))


def test_check_sample_reads_a_back_half_row():
    """The sample holds the longest request and, where a cohort had two
    rows or more, a row from its back half, whatever the seed."""
    stamps = [serveloop.Stamp(rid=i, prompt_len=4, max_new=8 if i == 5
                              else 4, due=0.0, emitted=8 if i == 5 else 4,
                              row=i % 4, rows=4) for i in range(40)]
    w = serveloop.Window(t0=0.0, t_end=1.0, t_drained=1.0, stamps=stamps,
                         requests={}, token_log=[], lateness_s=[], steps=0)
    for seed in range(20):
        pick = serveloop.check_sample(w, 2 ** 31 + seed, 4)
        assert pick[0] == 5 and len(set(pick)) == 4
        assert any(stamps[r].back_half for r in pick)
    assert not serveloop.Stamp(0, 4, 4, 0.0, row=0, rows=1).back_half


MIXES = {
    "open": {"loop": "open", "rate_rps": 3.0, "prompt_len": 512,
             "max_news": [32, 128], "max_new_probs": [0.7, 0.3],
             "capacity": 8},
    "closed": {"loop": "closed", "clients": 8, "prompt_len": 128,
               "max_news": [128, 512], "max_new_probs": [0.5, 0.5],
               "capacity": 8},
}


@pytest.mark.parametrize("loop", sorted(MIXES))
def test_traffic_is_a_function_of_the_seed(loop):
    mix = MIXES[loop]
    big = 2 ** 31 + 12345
    a = seedtraffic.Traffic(mix, big, 30.0, 1000)
    b = seedtraffic.Traffic(mix, big, 30.0, 1000)
    c = seedtraffic.Traffic(mix, big + 1, 30.0, 1000)
    n = 90 if loop == "open" else 64
    la = [a.lengths(i) for i in range(n)]
    assert la == [b.lengths(i) for i in range(n)]
    assert np.array_equal(a.prompt(5), b.prompt(5))
    # another seed: other prompts, the same work at the same times
    assert not np.array_equal(a.prompt(5), c.prompt(5))
    assert la == [c.lengths(i) for i in range(n)]
    assert len(set(la)) == 2
    if loop == "open":
        assert len(a) == 90
        assert np.array_equal(a.arrivals, b.arrivals)
        assert np.array_equal(a.arrivals, c.arrivals)
        assert len(set(np.round(np.diff(a.arrivals), 9))) > 80
        assert a.arrivals[0] == 0.0 and a.arrivals[-1] < 30.0
        assert sum(m == 128 for _, m in la) == 27       # 30 % of 90


def test_cohort_shapes():
    open_ = seedtraffic.Traffic(MIXES["open"], 1, 10.0, 100)
    assert len(open_.cohort_shapes()) == 16
    lockstep = dict(MIXES["closed"], max_news=[64], max_new_probs=[1.0])
    assert seedtraffic.Traffic(lockstep, 1, 10.0, 100).cohort_shapes() == \
        [(8, 128, 64)]


def _conf(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files_and_metrics():
    """Each cell's configuration, mix and per-layer readers are found by
    name, and each per-layer metric is held to an end-to-end metric
    that its cells report."""
    import re

    import cellspec
    bench = _bench()
    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$").match
    for entry in (bench["configs"] + bench["workloads"]
                  + bench["end_to_end"] + bench["per_layer"]):
        assert name_ok(entry["name"]), entry["name"]
        assert 0 < len(entry.get("why", "x")) <= 200, entry["name"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", entry.get("unit", "x"))
    cells = [w["name"] for w in bench["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in bench["end_to_end"]}
    for name in cells:
        cell = cellspec.Cell(ROOT, name)
        assert cell.per_layer and len(cell.end_to_end) >= 2
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end:
            assert m["name"].split(".")[0] in (
                "ttft_p95_ms", "tpot_p95_ms", "tokens_per_s", "setup_s")
    for m in bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= reports[m["moves"]], m
        assert callable(cellspec.reader(ROOT, m["name"]))


@pytest.mark.parametrize("metric, file", [
    ("idle_share", "idle_share"),
    ("idle_share.ep4", "idle_share"),
    ("mfu.decode.ep4", "mfu.decode"),
    ("mfu.ep4", "mfu"),
])
def test_reader_falls_back_to_the_name_before_a_dot(metric, file, tmp_path):
    """A metric's reader is ``metrics/<name>.py``, else the file named by
    its name with dotted parts taken off from the end; no file at all is
    an error."""
    import cellspec
    metrics = tmp_path / "benchmarks" / "chip" / "metrics"
    metrics.mkdir(parents=True)
    for name in ("idle_share", "mfu", "mfu.decode"):
        (metrics / f"{name}.py").write_text(
            f"def read(run):\n    return {name!r}\n")
    assert cellspec.reader(str(tmp_path), metric)(None) == file
    with pytest.raises(FileNotFoundError):
        cellspec.reader(str(tmp_path), "step_ms.ep4")


def test_readers_on_a_small_trace():
    """Every per-layer reader, on the small trace with a prefill of 2 x 4
    tokens and a decode of 2 rows of a tiny model: a number where the
    slice holds its work, shares of a peak under 100 %."""
    import cellspec
    import traced
    shape = counts.Shape(d=64, layers=2, heads=4, kv_heads=2, head_dim=16,
                         ffn=128, vocab=4096, experts=0, top_k=0, tied=True,
                         token_table=False, chips=1)
    peaks = json.load(open(os.path.join(CHIP, "peaks.json")))["TPU v5 lite"]
    calls = [serveloop.Call("prefill", 2, 4, 10e-6, 410e-6),
             serveloop.Call("decode", 2, 5, 500e-6, 700e-6)]
    spans = [("sched.step", 0.0, 700e-6),
             ("engine.start_cohort", 10e-6, 410e-6),
             ("engine.step_cohort", 500e-6, 700e-6)]
    run = traced.TracedSlice(
        reduction=xplane.reduce(small_trace()), shape=shape, peaks=peaks,
        chips=1, calls=calls, spans=spans, decode_tokens=[5, 5])
    for m in _bench()["per_layer"]:
        value = cellspec.reader(ROOT, m["name"])(run)
        assert isinstance(value, float) and value >= 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100, m["name"]
    assert run.sched_host_ms() == pytest.approx(0.1)
    assert run.decode_device_ms() == pytest.approx(0.1)
    assert run.idle_share() == pytest.approx(60.0)
    empty = traced.TracedSlice(
        reduction=run.reduction, shape=shape, peaks=peaks, chips=1,
        calls=[], spans=[], decode_tokens=[])
    assert empty.mfu() is None and empty.decode_hbm_roofline() is None


@pytest.mark.parametrize("name", ["qwen2_vl_2b", "dbrx_132b"])
def test_counts_match_the_parameter_count(name):
    import cellspec
    from repro.models.api import param_count_shape_only
    conf = _conf(name)
    shape = counts.shape_of(conf)
    n = counts.param_count(shape)
    assert n == param_count_shape_only(cellspec.program_config(conf))
    # a one-row decode at an empty cache reads the chip's weights once
    chip_params = (n - (0 if shape.tied else shape.vocab * shape.d)
                   - shape.layers * 3 * shape.d * shape.ffn
                   * (shape.experts - shape.experts // shape.chips))
    gathered = shape.d if shape.token_table else 0
    assert counts.decode_bytes_per_chip(shape, 1, [0]) == \
        2 * (chip_params + gathered)
    # matrix products of a token: 2 FLOPs per weight it multiplies
    active = (n - shape.vocab * shape.d * (1 if shape.tied else 2)
              - shape.layers * 2 * shape.d - shape.d
              - shape.layers * 3 * shape.d * shape.ffn
              * (shape.experts - shape.top_k))
    assert counts.decode_token_flops(shape, 0) == \
        2 * active + counts.logits_flops(shape)
    assert counts.prefill_flops(shape, 2, 3) == 2 * (
        3 * 2 * active + counts.attention_flops(shape, 6)
        + counts.logits_flops(shape))


# -- tiny end-to-end runs ----------------------------------------------------

# The program keeps its KV cache in bf16 whatever the activations, so at
# this size decode logits lie up to about 0.01 from the float32
# reference, which moves no served token: the limit is 0.01.  The tiny
# MoE sends every token to all of its experts, so that no top-k choice
# can flip on that rounding.
TINY = {
    "tq": {"name": "tq", "model_type": "qwen2_vl", "hidden_size": 64,
           "intermediate_size": 128, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "vocab_size": 4096, "rope_theta": 1e6,
           "rope_scaling": {"type": "mrope", "mrope_section": [4, 2, 2]},
           "rms_norm_eps": 1e-5, "hidden_act": "silu",
           "tie_word_embeddings": True, "token_table": False,
           "chips": 1,
           "program": {"arch": "qwen2_vl_2b", "plan_policy": "auto",
                       "dtype": "float32",
                       "replace": {"n_layers": 2, "d_model": 64,
                                   "n_heads": 4, "n_kv_heads": 2,
                                   "d_ff": 128, "vocab": 4096,
                                   "mrope_sections": [4, 2, 2]}},
           "check": {"widest_gap": 0.01}},
    "td": {"name": "td", "model_type": "dbrx", "d_model": 64, "n_heads": 4,
           "n_layers": 2, "vocab_size": 4096,
           "attn_config": {"clip_qkv": None, "kv_n_heads": 2,
                           "rope_theta": 5e5},
           "ffn_config": {"ffn_hidden_size": 64, "moe_num_experts": 4,
                          "moe_top_k": 4},
           "ffn_act_fn": "silu", "tie_word_embeddings": False,
           "norm_eps": 1e-5, "token_table": True, "chips": 4,
           "program": {"arch": "dbrx_132b", "plan_policy": "auto",
                       "dtype": "float32",
                       "replace": {"n_layers": 2, "d_model": 64,
                                   "n_heads": 4, "n_kv_heads": 2,
                                   "d_ff": 64, "moe_d_ff": 64,
                                   "num_experts": 4, "top_k": 4,
                                   "vocab": 4096, "moe_capacity": 8.0}},
           "check": {"widest_gap": 0.01}},
}
TINY_MIXES = {
    "chat": {"loop": "open", "rate_rps": 20.0, "prompt_len": 16,
             "max_news": [4, 8], "max_new_probs": [0.7, 0.3], "capacity": 4},
    "batch": {"loop": "closed", "clients": 4, "prompt_len": 16,
              "max_news": [6], "max_new_probs": [1.0], "capacity": 4},
}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-shaped directory whose BENCHMARK.json has two tiny
    cells: ``tq.chat`` (one device) and ``td.batch`` (four)."""
    root = tmp_path_factory.mktemp("bench")
    chip = root / "benchmarks" / "chip"
    (chip / "configs").mkdir(parents=True)
    (chip / "traffic").mkdir()
    for name, conf in TINY.items():
        (chip / "configs" / f"{name}.json").write_text(json.dumps(conf))
    for name, mix in TINY_MIXES.items():
        (chip / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": n, "source": "tiny",
                         "file": f"benchmarks/chip/configs/{n}.json",
                         "reduced": [], "why": "tiny"} for n in TINY]
    bench["workloads"] = [
        {"name": "tq.chat", "config": "tq", "traffic": "chat", "chips": 1,
         "why": "tiny"},
        {"name": "td.batch", "config": "td", "traffic": "batch", "chips": 4,
         "why": "tiny"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


DRIVER = """
import json, sys
sys.path.insert(0, {chip!r}); sys.path.insert(0, {src!r})
import numpy as np
import run_cell
fault = {fault!r}
wrap = None
if fault == "token":
    class Altered:
        def __init__(self, engine): self.engine = engine
        def start_cohort(self, *a, **k):
            state, toks, wall = self.engine.start_cohort(*a, **k)
            return state, (toks + 1) % 4096, wall
        def step_cohort(self, *a, **k): return self.engine.step_cohort(*a, **k)
    wrap = Altered
if fault == "state":
    class Unchanged:
        def __init__(self, engine): self.engine = engine
        def start_cohort(self, *a, **k): return self.engine.start_cohort(*a, **k)
        def step_cohort(self, state, tokens):
            import jax, jax.numpy as jnp
            cache = jax.tree_util.tree_map(jnp.copy, state.cache)  # donated
            out = self.engine.step_cohort(state, tokens)
            state.cache = cache
            return out
    wrap = Unchanged
if fault == "half":
    class Half:
        def __init__(self, engine): self.engine = engine
        def start_cohort(self, prompts, *a, **k):
            half = (len(prompts) + 1) // 2
            prompts = np.concatenate([prompts[:half]] * 2)[:len(prompts)]
            return self.engine.start_cohort(prompts, *a, **k)
        def step_cohort(self, *a, **k): return self.engine.step_cohort(*a, **k)
    wrap = Half
if fault == "exchange":
    import types
    import jax
    from repro.core import collectives
    collectives.lax = types.SimpleNamespace(**dict(
        vars(jax.lax), all_to_all=lambda x, *a, **k: x))
out = run_cell.run({cell!r}, 2 ** 31 + 7, 2.0, False, root={root!r},
                   require_chip=False, wrap_engine=wrap)
print(json.dumps(out))
"""


def _drive(root, cell, fault=None, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = DRIVER.format(chip=CHIP, src=os.path.join(ROOT, "src"),
                         fault=fault, cell=cell, root=root)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_command_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run_cell.py"), "--workload",
         "qwen2_vl_2b.chat", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("cell,devices", [("tq.chat", 1), ("td.batch", 4)])
def test_tiny_run_is_correct(tiny_root, cell, devices):
    out = _drive(tiny_root, cell, devices=devices)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["widest_gap"]["value"] <= 0.01
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell,fault,devices", [
    ("tq.chat", "token", 1),
    ("tq.chat", "state", 1),
    ("td.batch", "token", 4),
    ("td.batch", "state", 4),
    ("td.batch", "half", 4),
    ("td.batch", "exchange", 4),
])
def test_fault_is_not_correct(tiny_root, cell, fault, devices):
    out = _drive(tiny_root, cell, fault=fault, devices=devices)
    assert out["correct"] is False, out
    assert out["checks"]["widest_gap"]["value"] > \
        out["checks"]["widest_gap"]["limit"]


@pytest.mark.parametrize("cell,devices", [("tq.chat", 1), ("td.batch", 4)])
def test_fp8_control_is_not_correct(tiny_root, cell, devices):
    """The float8 control's first choices, over a tiny run's served
    prompts, lie further below the exact reference's best than the
    cell's limit allows."""
    code = """
import json, sys
sys.path.insert(0, {chip!r}); sys.path.insert(0, {src!r})
import run_cell, control, reference, seedtraffic, serveloop, cellspec
cell = cellspec.Cell({root!r}, {cell!r})
run_cell.enable_cache({root!r})
engine = run_cell.build(cell, 5)
traffic = seedtraffic.Traffic(cell.mix, 5, 2.0, cell.conf["vocab_size"])
run_cell.warm(engine, traffic.cohort_shapes())
spans = serveloop.Spans()
w = serveloop.serve_window(serveloop.TimedEngine(engine, spans), traffic,
                           2.0, spans)
ref = reference.Reference(cell.conf, engine.params)
print(json.dumps(control.readings(ref, w, 5, max(cell.mix["max_news"]))))
""".format(chip=CHIP, src=os.path.join(ROOT, "src"), root=tiny_root,
           cell=cell)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    limit = TINY[cell.split(".")[0]]["check"]["widest_gap"]
    assert row["program_widest_gap"] <= limit
    assert row["control_widest_gap"] > limit
