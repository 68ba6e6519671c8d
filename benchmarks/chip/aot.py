"""Compile a cell's largest prefill and decode for a described TPU, with
no chip attached, and print what the compiler says they hold.

  JAX_PLATFORMS=cpu python3 benchmarks/chip/aot.py --workload <cell>

The serving stack is built as ``run_cell.py`` builds it, but over the
devices of a described ``v5e:2x2`` host (the first of them for a
one-chip cell) and over parameter shapes, not arrays.  Prints one JSON
line per program with ``memory_analysis()``'s sizes in bytes.
"""

import argparse
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import cellspec  # noqa: E402
import seedtraffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="compile at this prompt length instead of the mix's")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from repro.launch import serve
    from repro.models.api import build_model
    from repro.parallel import sharding as shd

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cellspec.Cell(ROOT, args.workload)
    if args.prompt_len:
        cell.mix = dict(cell.mix, prompt_len=args.prompt_len)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices[:cell.chips])
    conf, prog = cell.conf, cell.conf["program"]
    mcfg = cellspec.program_config(conf)
    dtype = jnp.dtype(prog["dtype"])
    b, s, max_new = max(seedtraffic.Traffic(
        cell.mix, 0, 1.0, conf["vocab_size"]).cohort_shapes())
    sargs = serve.parse_args([
        "--arch", prog["arch"], "--prompts", str(cell.mix["capacity"]),
        "--prompt-len", str(s), "--max-new", str(max_new),
        "--plan-policy", prog["plan_policy"]])
    with mock.patch.object(jax, "devices", lambda *a: devices):
        pctx = None
        if cell.chips > 1:
            from repro.launch.mesh import make_pctx
            pctx = make_pctx(fsdp=False, plan_policy=sargs.plan_policy,
                             moe_scheme=sargs.moe_scheme)
        init = build_model(mcfg, pctx, dtype=dtype).init
        shapes = jax.eval_shape(init, jax.random.key(0))
        if pctx is None:
            one = SingleDeviceSharding(devices[0])
            p_sh = jax.tree_util.tree_map(lambda _: one, shapes)
            rep = one
        else:
            p_sh = shd.named(shd.param_specs(shapes, mcfg, pctx), pctx)
            rep = NamedSharding(pctx.mesh, P())
        params = jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, dtype, sharding=sh),
            shapes, p_sh)
        engine = serve.build_engine(sargs, mcfg, params, dtype)
        model = engine._binder.artifact.model

        def abstract(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=rep), tree)

        cache = abstract(jax.eval_shape(
            lambda: model.init_cache(b, s + max_new, jnp.bfloat16)))
        if mcfg.input_mode == "embeddings":
            pre = {"embeds": jax.ShapeDtypeStruct((b, s, mcfg.d_model),
                                                  jnp.float32, sharding=rep),
                   "positions": jax.ShapeDtypeStruct((b, s, 3), jnp.int32,
                                                     sharding=rep)}
            dec = {"embeds": jax.ShapeDtypeStruct((b, 1, mcfg.d_model),
                                                  jnp.float32, sharding=rep)}
        else:
            pre = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32,
                                                  sharding=rep)}
            dec = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                                  sharding=rep)}
        for name, fn, batch in (("prefill", engine._prefill, pre),
                                ("decode", engine._decode, dec)):
            mem = fn.lower(params, batch, cache).compile().memory_analysis()
            row = {"workload": args.workload, "program": name,
                   "shape": [b, s, max_new], "chips": cell.chips}
            for key in ("argument_size_in_bytes", "output_size_in_bytes",
                        "alias_size_in_bytes", "temp_size_in_bytes",
                        "generated_code_size_in_bytes"):
                row[key] = int(getattr(mem, key))
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
