"""Finding a cell's files by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration and traffic mix.  A configuration is the file its entry
in ``configs`` gives; a traffic mix is ``traffic/<name>.json`` beside
this file; a per-layer metric's reader is ``metrics/<name>.py`` (see
``reader``).  A cell added later brings its own files and edits none of
these.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, its mix and the
    metrics it reports."""

    def __init__(self, root: str, name: str):
        self.root = root
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; there "
                           f"are {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.conf = load_json(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        self.mix = load_json(os.path.join(
            root, "benchmarks", "chip", "traffic",
            self.entry["traffic"] + ".json"))
        self.chips = int(self.entry["chips"])
        if self.conf["chips"] != self.chips:
            raise ValueError(f"{name}: the cell asks for {self.chips} chips, "
                             f"its configuration for {self.conf['chips']}")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if self._reports(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if self._reports(m) and m["moves"] in moved]

    def _reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])


def reader(root: str, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``, or, where there
    is no such file, of the file named by the metric's name with its
    last dotted part taken off, and so on: ``idle_share.ep4`` is read by
    ``metrics/idle_share.py`` unless ``metrics/idle_share.ep4.py``
    exists."""
    base = os.path.join(root, "benchmarks", "chip", "metrics")
    name = metric
    while not os.path.exists(os.path.join(base, name + ".py")):
        if "." not in name:
            raise FileNotFoundError(f"no reader for {metric!r} in {base}")
        name = name.rsplit(".", 1)[0]
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        os.path.join(base, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ModelConfig field of the program <- how to read it from the file
_FIELDS = {
    "d_model": lambda c: c.get("hidden_size", c.get("d_model")),
    "n_layers": lambda c: c.get("num_hidden_layers", c.get("n_layers")),
    "n_heads": lambda c: c.get("num_attention_heads", c.get("n_heads")),
    "n_kv_heads": lambda c: c.get("num_key_value_heads",
                                  c.get("attn_config", {}).get("kv_n_heads")),
    "head_dim": lambda c: c.get("head_dim", c.get("d_model", 0)
                                // max(1, c.get("n_heads", 1))),
    "vocab": lambda c: c["vocab_size"],
    "rope_theta": lambda c: float(c.get("rope_theta", c.get(
        "attn_config", {}).get("rope_theta", 0))),
    "tie_embeddings": lambda c: c["tie_word_embeddings"],
    "norm_eps": lambda c: c.get("rms_norm_eps", c.get("norm_eps")),
    "act": lambda c: c.get("hidden_act", c.get("ffn_act_fn")),
}


def program_config(conf: dict):
    """The program's ModelConfig for a configuration file, checked field
    by field against the file, so that the program cannot run other
    sizes than the file states."""
    import dataclasses

    from repro.configs.base import get_config
    prog = conf["program"]
    replace = {k: tuple(v) if isinstance(v, list) else v
               for k, v in prog["replace"].items()}
    cfg = dataclasses.replace(get_config(prog["arch"]), **replace)
    want = {k: f(conf) for k, f in _FIELDS.items()}
    ffn = conf.get("ffn_config")
    if ffn is not None:
        want.update(expert_d_ff=ffn["ffn_hidden_size"],
                    num_experts=ffn["moe_num_experts"],
                    top_k=ffn["moe_top_k"])
    else:
        want.update(d_ff=conf["intermediate_size"], num_experts=0)
    scaling = conf.get("rope_scaling")
    if scaling and scaling.get("mrope_section"):
        want["mrope_sections"] = tuple(scaling["mrope_section"])
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if bad:
        raise ValueError(f"{conf['name']}: the program's config differs from "
                         f"the file (program, file): {bad}")
    return cfg
