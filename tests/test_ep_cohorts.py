"""Cohorts of any size on the four-device expert-parallel mesh.

A tiny DBRX with the published expert count and top-k (16 experts, top
4: real routing, whose choices a padded row or a lost token would move)
is served in float32 through ``serve.build_engine`` on four forced host
devices (the 2x2 pod x data mesh), in one subprocess so that the device
count never reaches this process.  Its logits are compared with the
chip benchmark's plain reference (``benchmarks/chip/reference.py``),
which shares no code with the program.  Each test below reads one part
of the subprocess's JSON.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROMPT_LEN = 7            # b * 7 rows divide over 4 ranks only for b = 8
COHORTS = (1, 2, 3, 5, 8)
# float32 activations, weights and KV cache on both sides: the two differ
# by the order of float32 sums, about 1e-6 at this size.  A flipped
# top-4 choice, or a padded row that took a real row's slot, moves a
# logit by 1e-2 or more.
TOLERANCE = 1e-4

SCRIPT = r"""
import dataclasses, json, sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, {chip!r})
import reference
from repro.configs.base import get_config
from repro.launch import serve
from repro.launch.mesh import make_pctx
from repro.models import moe
from repro.models.api import build_model
from repro.runtime.server import ServeConfig, ServeEngine
from repro.telemetry import spans

S, NEW, VOCAB = {prompt_len}, 3, 512
cfg = dataclasses.replace(get_config("dbrx_132b").reduced(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=64, vocab=VOCAB),
    num_experts=16, top_k=4, moe_capacity=4.0)
conf = {{"name": "tiny", "model_type": "dbrx", "d_model": 64, "n_heads": 4,
        "n_layers": 2, "vocab_size": VOCAB,
        "attn_config": {{"clip_qkv": None, "kv_n_heads": 2,
                        "rope_theta": cfg.rope_theta}},
        "ffn_config": {{"ffn_hidden_size": 64, "moe_num_experts": 16,
                       "moe_top_k": 4}},
        "ffn_act_fn": "silu", "tie_word_embeddings": False,
        "norm_eps": cfg.norm_eps, "token_table": True, "chips": 4}}
rng = np.random.default_rng(3)
prompts = {{b: rng.integers(0, VOCAB, size=(b, S)).astype(np.int32)
           for b in {cohorts}}}


def engine_for(policy, capacity=4.0, scheme="hierarchical"):
    args = serve.parse_args(["--arch", "dbrx_132b", "--smoke", "--prompts",
                             "8", "--prompt-len", str(S), "--max-new",
                             str(NEW), "--plan-policy", policy,
                             "--moe-scheme", scheme])
    engine = serve.build_engine(
        args, dataclasses.replace(cfg, moe_capacity=capacity))
    engine.cfg.cache_dtype = jnp.float32
    return engine


def serve_cohort(engine, rows):
    # every launch's logits and greedy tokens, and its span attributes
    spans.enable()
    state, toks, _ = engine.start_cohort(rows, max_new=NEW)
    logits, served = [np.asarray(state.logits, np.float32)], [toks]
    for _ in range(NEW - 1):
        state, toks, _ = engine.step_cohort(state, toks)
        logits.append(np.asarray(state.logits, np.float32))
        served.append(toks)
    spans.disable()
    attrs = [r.attrs for r in spans.drain()
             if r.name in ("engine.prefill", "engine.decode")]
    return np.stack(logits, 1), np.stack(served, 1), attrs


def reference_error(engine, rows, logits, served):
    ref = reference.Reference(conf, engine.params)
    return max(float(np.abs(ref.logits(rows[r], served[r])
                            - logits[r]).max()) for r in range(len(rows)))


out = {{"mesh": None, "cohorts": {{}}, "launches": {{}}}}
for policy, scheme, sizes in (("auto", "hierarchical", {cohorts}),
                              ("fixed", "hierarchical", (3,)),
                              ("fixed", "baseline", (3,))):
    engine = engine_for(policy, scheme=scheme)
    out["mesh"] = dict(engine.pctx.mesh.shape)
    for b in sizes:
        logits, served, attrs = serve_cohort(engine, prompts[b])
        key = f"{{policy}}-{{scheme}}-{{b}}"
        out["cohorts"][key] = reference_error(engine, prompts[b], logits,
                                              served)
        out["launches"][key] = attrs
        if key == "auto-hierarchical-3":
            out["mesh_tokens"] = served.tolist()
            host = jax.tree_util.tree_map(np.asarray, engine.params)

# the same three rows on one device, no mesh
one = ServeEngine(build_model(cfg, None, dtype=jnp.float32),
                  jax.tree_util.tree_map(jnp.asarray, host),
                  ServeConfig(max_new_tokens=NEW, cache_dtype=jnp.float32))
out["one_device_tokens"] = serve_cohort(one, prompts[3])[1].tolist()

# a capacity of a sixteenth of the balanced load leaves tokens without a
# slot; the counter must see them
planted = engine_for("auto", capacity=0.25)
out["launches"]["planted"] = serve_cohort(planted, prompts[5])[2]

# moe_ffn alone: a padded batch's rows equal the same rows in a batch
# that divides, with G = 2 chunks too; only the padded batch's program
# pads and masks
layer = jax.tree_util.tree_map(lambda a: a[0],
                               engine.params["layers"]["moe"])
x = jnp.asarray(rng.normal(size=(8, S, 64)), jnp.float32)
pipe = {{}}
for scheme, g in (("hierarchical", 1), ("hierarchical", 2),
                  ("baseline", 2)):
    pctx = dataclasses.replace(make_pctx(fsdp=False, plan_policy="fixed",
                                         moe_scheme=scheme),
                               moe_microbatch=g)
    f = lambda x: moe.moe_ffn(layer, x, cfg, pctx)
    padded, _, dropped3 = jax.jit(f)(x[:3])
    whole, _, dropped4 = jax.jit(f)(x)
    pipe[f"{{scheme}}-{{g}}"] = {{
        "error": float(jnp.abs(padded - whole[:3]).max()),
        "dropped": [int(np.sum(dropped3)), int(np.sum(dropped4))],
        "pads": [str(jax.make_jaxpr(f)(x[:3])).count("pad["),
                 str(jax.make_jaxpr(f)(x)).count("pad[")],
        "plans": [moe.plan_moe(cfg, pctx, 3 * S, 64, 64, 4).attrs(),
                  moe.plan_moe(cfg, pctx, 8 * S, 64, 64, 4).attrs()]}}
out["moe_ffn"] = pipe

# the worst routing the cap must survive: every token picks experts 0-3,
# all on the rank at (pod 0, data 0), so every stage fills to its
# no-drop worst case; the one-device layer is the comparison
bias = jnp.zeros((64, 16)).at[0, :4].set(100.0)
forced = dict(layer, router=0.01 * layer["router"] + bias)
xf = x.at[..., 0].set(1.0)
alone, _, dropped1 = moe.moe_ffn(forced, xf, cfg, None)
worst = {{"one_device_dropped": int(np.sum(dropped1))}}
for scheme in ("hierarchical", "baseline"):
    pctx = make_pctx(fsdp=False, plan_policy="fixed", moe_scheme=scheme)
    ids = jax.lax.top_k(xf.reshape(-1, 64) @ forced["router"], 4)[1]
    got, _, dropped = jax.jit(
        lambda x: moe.moe_ffn(forced, x, cfg, pctx))(xf)
    worst[scheme] = {{
        "experts": sorted({{int(e) for e in np.asarray(ids).ravel()}}),
        "error": float(jnp.abs(got - alone).max()),
        "dropped": int(np.sum(dropped)),
        "slots": moe.plan_moe(cfg, pctx, 8 * S, 64, 64, 4).attrs()}}
out["worst_case"] = worst

# the dry run's out_shardings take every family's (logits, cache, stats)
# on the mesh: a dense stack's empty stats and the MoE drop counts
from repro.launch.dryrun import serve_out_shardings
from repro.parallel import sharding as shd
pctx = engine.pctx
dense = get_config("mistral_nemo_12b").reduced(n_layers=2, vocab=VOCAB)
lowered = {{}}
for name, c in (("dbrx", cfg), ("dense", dense)):
    model = build_model(c, pctx, dtype=jnp.float32)
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(lambda: model.init_cache(3, S + 1, jnp.float32))
    cache = shd.with_sharding(cache, shd.cache_specs(cache, c, pctx), pctx)
    shardings = serve_out_shardings(c, pctx, 3, cache)
    for kind, step, width in (("prefill", model.prefill, S),
                              ("decode", model.decode, 1)):
        batch = {{"tokens": jax.ShapeDtypeStruct((3, width), jnp.int32)}}
        lo = jax.jit(step, out_shardings=shardings).lower(params, batch,
                                                          cache)
        lowered[f"{{name}}-{{kind}}"] = sorted(lo.out_info[2])
out["dryrun"] = lowered
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    code = SCRIPT.format(chip=os.path.join(REPO, "benchmarks", "chip"),
                         prompt_len=PROMPT_LEN, cohorts=COHORTS)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["mesh"] == {"pod": 2, "data": 2, "model": 1}
    return out


CASES = ([f"auto-hierarchical-{b}" for b in COHORTS]
         + ["fixed-hierarchical-3", "fixed-baseline-3"])


@pytest.mark.parametrize("case", CASES)
def test_cohort_matches_the_reference(served, case):
    """Prefill and every decode step of a cohort of 1, 2, 3, 5 or 8
    rows, with prompts of 7 tokens, give the reference's logits: the
    planner's choice, and MultiWrite and unicast dispatch pinned."""
    assert served["cohorts"][case] < TOLERANCE


@pytest.mark.parametrize("case", CASES)
def test_launch_records_its_plan_and_drops(served, case):
    """Each prefill and decode span carries the rows padded on, the
    plan its layers ran, the slots its dispatch stages allocate, and no
    dropped pair at capacity 4.0."""
    b = int(case.rsplit("-", 1)[1])
    launches = served["launches"][case]
    assert [a["ep_pad_rows"] for a in launches] == \
        [-b * PROMPT_LEN % 4] + [-b % 4] * (len(launches) - 1)
    for a in launches:
        assert a["moe_dropped"] == 0
        # 16 experts top 4 over 2 pods x 2 ranks at capacity 4.0: every
        # stage sits at the most rows that can arrive in the launch's
        # chunk of c rows a chip (unicast: c a rank, 4c an expert)
        rows = b * PROMPT_LEN if a is launches[0] else b
        c = (rows + a["ep_pad_rows"]) // 4 // a["moe_g"]
        assert a["moe_slots"] == (f"{c}/{2 * c}/{4 * c}"
                                  if a["moe_dispatch"] == "hierarchical"
                                  else f"{c}/{4 * c}")
        assert a["moe_dispatch"] in ("hierarchical", "baseline")
        assert a["moe_combine"] in ("hierarchical", "baseline")
        assert a["moe_g"] >= 1
    if case.startswith("fixed"):
        assert {a["moe_dispatch"] for a in launches} == \
            {case.split("-")[1]}


def test_cohort_of_three_matches_one_device(served):
    """The greedy tokens of three rows served across the mesh are those
    the same rows get on one device without a mesh."""
    assert served["mesh_tokens"] == served["one_device_tokens"]


def test_planted_capacity_drops_are_counted(served):
    assert all(a["moe_dropped"] > 0 for a in served["launches"]["planted"])


@pytest.mark.parametrize("case", ["hierarchical-1", "hierarchical-2",
                                  "baseline-2"])
def test_padding_only_where_rows_do_not_divide(served, case):
    """moe_ffn on 21 rows (3 x 7, padded to 24) gives the rows it gives
    in a batch of 56 that divides, whole or in G = 2 chunks, and drops
    nothing; the program pads only the batch that does not divide."""
    row = served["moe_ffn"][case]
    assert row["error"] < TOLERANCE
    assert row["dropped"] == [0, 0]
    assert row["pads"][0] > 0 and row["pads"][1] == 0
    assert [p["ep_pad_rows"] for p in row["plans"]] == [3, 0]
    assert [p["moe_g"] for p in row["plans"]] == [int(case[-1])] * 2


@pytest.mark.parametrize("scheme", ["hierarchical", "baseline"])
def test_worst_case_routing_drops_nothing(served, scheme):
    """A router planted so that every token picks the same 4 experts,
    all on one rank, fills each stage to the most rows that can arrive:
    at capacity 4.0 the slots sized to that worst case hold them all,
    and the rows match the one-device layer's."""
    worst = served["worst_case"]
    row = worst[scheme]
    assert row["experts"] == [0, 1, 2, 3]
    assert row["dropped"] == 0 and worst["one_device_dropped"] == 0
    assert row["error"] < TOLERANCE
    assert row["slots"]["moe_dispatch"] == scheme
    assert row["slots"]["moe_slots"] == ("14/28/56" if scheme ==
                                         "hierarchical" else "14/56")


@pytest.mark.parametrize("case", ["dbrx-prefill", "dbrx-decode",
                                  "dense-prefill", "dense-decode"])
def test_dry_run_shardings_lower_every_family(served, case):
    """The dry run's ``out_shardings`` lower a prefill and a decode of
    three rows on the mesh: the MoE step's drop counts and a dense
    step's empty counters alike."""
    stats = ["moe_dropped"] if case.startswith("dbrx") else []
    assert served["dryrun"][case] == stats
