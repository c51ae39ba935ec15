"""A throwaway checkout for the harness's CPU tests: a copy of
``chipbench/`` with the test-only configuration, mix and cell of
``data/`` dropped in as new files, and a BENCHMARK.json with their
entries appended."""
from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")
CELL = "tiny.docqa"


def make_checkout(root: str, dtype: str = "float32",
                  extra_metric: str = "", top_k: int = 0) -> str:
    """Build the checkout under ``root``; returns its BENCHMARK.json.
    ``top_k``, where given, replaces the configuration's
    ``top_k_chunks``."""
    shutil.copytree(BENCH, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(DATA, "tiny.json")) as f:
        conf = json.load(f)
    conf["torch_dtype"] = dtype
    if top_k:
        conf["moska"]["top_k_chunks"] = top_k
    with open(os.path.join(root, "chipbench", "configs", "tiny.json"),
              "w") as f:
        json.dump(conf, f)
    shutil.copy(os.path.join(DATA, "tiny-traffic.json"),
                os.path.join(root, "chipbench", "traffic", "tiny.json"))
    shutil.copy(os.path.join(DATA, "tiny-cell.json"),
                os.path.join(root, "chipbench", "cells", f"{CELL}.json"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "test only",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test only"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny", "chips": 1,
                               "why": "test only"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    if extra_metric:
        with open(os.path.join(root, "chipbench", "metrics",
                               f"{extra_metric}.py"), "w") as f:
            f.write("def read(data):\n    return float(len(data.recs))\n")
        bench["end_to_end"].append({
            "name": extra_metric, "unit": "requests", "better": "higher",
            "bound": 0.25, "source": "host_clock", "workloads": [CELL]})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
