"""The benchmark's data, found by name: ``BENCHMARK.json`` at the root of the
checkout, and under this directory one file per configuration
(``configs/<name>.json``), per traffic mix (``traffic/<name>.json``), per
cell (``workloads/<name>.json``) and per per-layer metric
(``metrics/<name>.py``).  Nothing here imports JAX or the program."""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

@dataclass(frozen=True)
class Model:
    """A dense decoder as its configuration file states it."""

    name: str
    reference: str          # module under references/ that computes it
    program_arch: str       # the program's registered architecture
    layers: int
    published_layers: int   # depth of the published model
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    act: str                # "gelu_tanh"
    norm: str               # "layernorm"
    norm_eps: float
    rope_theta: float
    dtype: str              # parameter and activation type, "bfloat16"

    @classmethod
    def from_file(cls, cfg: dict) -> "Model":
        return cls(name=cfg["name"], reference=cfg["reference"],
                   program_arch=cfg["program_arch"],
                   layers=cfg["num_hidden_layers"],
                   published_layers=cfg["published"]["num_hidden_layers"],
                   d=cfg["hidden_size"],
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg["head_dim"], ff=cfg["intermediate_size"],
                   vocab=cfg["vocab_size"], act=cfg["hidden_act"],
                   norm=cfg["norm"], norm_eps=cfg["norm_eps"],
                   rope_theta=cfg["rope_theta"], dtype=cfg["dtype"])

    @property
    def d_attn(self) -> int:
        return self.heads * self.head_dim


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names."""

    name: str
    chips: int
    model: Model
    traffic: dict
    mesh: dict              # {"data": n, "model": n}
    plan: dict              # overrides of the program's resolved plan
    optimizer: dict         # AdamW as the configuration states it
    checked_steps: int      # steps set-up drives and the reference follows
    limits: dict            # compared number -> limit
    end_to_end: tuple       # names of the end-to-end metrics it reports
    per_layer: tuple        # names of the per-layer metrics it reports


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(w['name'] for w in bench['workloads'])}")
    conf = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    here = os.path.join(root, "benchmarks", "chip")
    cfg = _read_json(os.path.join(root, conf["file"]))
    traffic = _read_json(os.path.join(here, "traffic",
                                      entry["traffic"] + ".json"))
    work = _read_json(os.path.join(here, "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if work[key] != entry[key]:
            raise ValueError(f"workloads/{name}.json says {key} "
                             f"{work[key]!r}, BENCHMARK.json {entry[key]!r}")
    e2e = tuple(m["name"] for m in bench["end_to_end"] if _reports(m, name))
    layer = tuple(m["name"] for m in bench["per_layer"] if _reports(m, name))
    return Cell(name=name, chips=entry["chips"], model=Model.from_file(cfg),
                traffic=traffic, mesh=work["mesh"], plan=work["plan"],
                optimizer=work["optimizer"],
                checked_steps=work["checked_steps"],
                limits=work["limits"], end_to_end=e2e, per_layer=layer)


def units(root: str = ROOT) -> dict:
    bench = benchmark(root)
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}
