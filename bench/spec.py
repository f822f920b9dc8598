"""Find a cell's parts by name and check that they are all there.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
each per-layer metric.  Each part is a file of its own under ``bench/``:

* ``bench/configs/<config>.json`` (or the ``file`` its entry names): the
  sizes as run, the registry model they select, the engine settings;
* ``bench/traffic/<traffic>.json``: the parameters of one traffic mix,
  read by the one generator in ``bench/traffic.py`` (the program's stages
  are data there too);
* ``bench/backends/<backend>.py``, named by the configuration's
  ``"backend"`` (default ``local``): a builder with ``build(model,
  params, conf, tokenizer, devices)`` that puts the serving stack
  together (one engine, a fleet of replicas, ...);
* ``bench/metrics/<metric>.py``, for every metric, end-to-end and per
  layer: a reader with ``read(run) -> float | None`` (``None``: nothing
  to read in this run, the metric is left out).

A later cell is a new ``workloads`` entry plus new files; no file here
changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bm['workloads']]}")


def _config_entry(bm: dict | None, name: str) -> dict | None:
    for c in (bm or {}).get("configs", ()):
        if c["name"] == name:
            return c
    return None


def config_path(name: str, bm: dict | None = None, root: Path = ROOT) -> Path:
    entry = _config_entry(bm, name)
    if entry is not None:
        return Path(root) / entry["file"]
    return Path(root) / "bench" / "configs" / f"{name}.json"


def load_config(name: str, bm: dict | None = None, root: Path = ROOT) -> dict:
    conf = json.loads(config_path(name, bm, root).read_text())
    conf.setdefault("name", name)
    return conf


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "traffic" / f"{name}.json"


def load_traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads(traffic_path(name, root).read_text())


def metric_path(name: str, root: Path = ROOT) -> Path:
    return Path(root) / "bench" / "metrics" / f"{name}.py"


def backend_path(conf: dict, root: Path = ROOT) -> Path:
    return (Path(root) / "bench" / "backends"
            / f"{conf.get('backend', 'local')}.py")


def _module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, root: Path = ROOT):
    """The ``read`` function of metric ``name``'s reader file."""
    return _module(metric_path(name, root), f"bench_metric_{name}").read


def load_builder(conf: dict, root: Path = ROOT):
    """The ``build`` function of the configuration's backend file."""
    path = backend_path(conf, root)
    return _module(path, f"bench_backend_{path.stem}").build


def cell_metrics(bm: dict, name: str) -> tuple[list, list]:
    """(end-to-end, per-layer) metric entries that cell ``name`` reports:
    those without a ``workloads`` key, and those that list it."""
    def mine(m):
        return "workloads" not in m or name in m["workloads"]
    return ([m for m in bm["end_to_end"] if mine(m)],
            [m for m in bm["per_layer"] if mine(m)])


def validate(bm: dict, root: Path = ROOT) -> list[str]:
    """Every fault that keeps a cell from running, as plain sentences
    (empty when the benchmark is whole)."""
    faults = []
    configs = {c["name"] for c in bm["configs"]}
    e2e = {m["name"] for m in bm["end_to_end"]}
    for w in bm["workloads"]:
        if w["config"] not in configs:
            faults.append(f"{w['name']}: config {w['config']!r} has no "
                          f"entry in configs")
        elif not config_path(w["config"], bm, root).is_file():
            faults.append(f"{w['name']}: no configuration file for "
                          f"{w['config']!r}")
        else:
            conf = load_config(w["config"], bm, root)
            if not backend_path(conf, root).is_file():
                faults.append(f"{w['name']}: no backend file for "
                              f"{conf.get('backend', 'local')!r}")
            if traffic_path(w["traffic"], root).is_file():
                faults += [f"{w['name']}: {f}" for f in _mix_faults(
                    load_traffic(w["traffic"], root), conf,
                    bm.get("run_seconds", 10))]
        if not traffic_path(w["traffic"], root).is_file():
            faults.append(f"{w['name']}: no traffic file for "
                          f"{w['traffic']!r}")
        ends, layers = cell_metrics(bm, w["name"])
        names = {m["name"] for m in ends}
        if "setup_s" not in names or len(names) < 2:
            faults.append(f"{w['name']}: reports {sorted(names)}; needs "
                          f"setup_s and one more end-to-end metric")
        if not layers:
            faults.append(f"{w['name']}: reports no per-layer metric")
        for m in layers:
            if m["moves"] not in names:
                faults.append(f"{w['name']}: {m['name']} moves "
                              f"{m['moves']}, which the cell does not "
                              f"report")
    for m in bm["per_layer"]:
        if m["moves"] not in e2e:
            faults.append(f"{m['name']} moves unknown metric {m['moves']}")
    for m in bm["end_to_end"] + bm["per_layer"]:
        if not metric_path(m["name"], root).is_file():
            faults.append(f"{m['name']}: no reader file")
    for c in bm["configs"]:
        if not (Path(root) / c["file"]).is_file():
            faults.append(f"config {c['name']}: missing file {c['file']}")
    return faults


def _mix_faults(mix: dict, conf: dict, seconds: float) -> list[str]:
    """Whether the generator reads the mix, and every request fits the
    engine's ``max_len``."""
    from bench import traffic
    try:
        sched = traffic.schedule(mix, 1, seconds, conf["vocab_size"])
    except (KeyError, TypeError, ValueError, IndexError) as e:
        return [f"the generator cannot read its traffic mix ({e!r})"]
    worst = max(traffic.longest_request(p) for p in sched)
    if worst >= conf["engine"]["max_len"]:
        return [f"a request needs {worst} positions, the engine holds "
                f"{conf['engine']['max_len']}"]
    return []


def model_config(conf: dict, *, strict: bool = True):
    """The program's ``ModelConfig`` for a configuration file: the
    registry model at the file's sizes.  With ``strict`` (every chip run)
    a width that differs from the registry's is an error, never a silent
    change; tests turn it off to run the same model at toy widths."""
    from repro.configs import get_config

    cfg = get_config(conf["registry"])
    want = {"d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
            "num_heads": conf["num_attention_heads"],
            "num_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["hidden_size"] // conf["num_attention_heads"],
            "vocab_size": conf["vocab_size"],
            "rope_theta": float(conf["rope_theta"]),
            "norm_type": conf["norm"],
            "tie_embeddings": conf["tie_word_embeddings"]}
    got = {k: getattr(cfg, k) for k in want}
    if strict and got != want:
        raise ValueError(f"registry {conf['registry']!r} is not the "
                         f"configuration file: {got} != {want}")
    eps = conf.get("layer_norm_eps", conf.get("rms_norm_eps"))
    return cfg.replace(num_layers=conf["num_hidden_layers"],
                       norm_eps=float(eps), **want)
