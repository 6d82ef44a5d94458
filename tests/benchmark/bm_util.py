"""Shared by the benchmark's tests: a temporary copy of ``benchmark/`` with
the tiny test configurations laid over it (files added, none edited)."""
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny")
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def tiny_root(tmp_path):
    """``<tmp>/benchmark`` holding every file of ``benchmark/`` untouched
    plus the tiny configuration, traffic, cell and metric files."""
    root = os.path.join(str(tmp_path), "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark"), root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    shutil.copytree(TINY, root, dirs_exist_ok=True)
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"
    return root


SEED = 2147483659


def run_tiny(root, cell_name, seconds=0.5, trace=0, seed=SEED):
    from benchmark import harness, run
    cell = harness.load_cell(cell_name, root)
    return run.run_cell(cell, seed, seconds, trace, root=root,
                        t_start=time.perf_counter())
