"""Generate docs/API.md — an index of the public API with first-line
docstrings. Re-run after adding exports:

    JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python tools/gen_api_docs.py
"""
from __future__ import annotations

import inspect
import os

SECTIONS = [
    ("bigdl_tpu.nn", "Layers, criteria, containers, graphs"),
    ("bigdl_tpu.optim", "Optimizers, schedules, triggers, validation"),
    ("bigdl_tpu.keras", "Keras-1.2 style front-end"),
    ("bigdl_tpu.ops", "TF-style ops"),
    ("bigdl_tpu.parallel", "Mesh/collectives/ZeRO/ring/pipeline/MoE"),
    ("bigdl_tpu.dataset", "DataSet/MiniBatch/loaders/text"),
    ("bigdl_tpu.transform.vision", "Image augmentation pipeline"),
    ("bigdl_tpu.models", "Model zoo"),
    ("bigdl_tpu.loaders", "Interop: caffe/t7/TF/bigdl.proto/keras-json"),
    ("bigdl_tpu.quantization", "Int8 PTQ + calibration"),
    ("bigdl_tpu.dlframes", "DataFrame pipeline stages"),
    ("bigdl_tpu.serving", "Online inference: micro-batching/buckets/"
     "backpressure/hot swap"),
    ("bigdl_tpu.visualization", "TensorBoard event writing"),
    ("bigdl_tpu.observability", "Tracing + metrics + exporters"),
    ("bigdl_tpu.utils", "Engine/Table/Shape/RNG/File/graph utils"),
    ("bigdl_tpu.kernels", "Pallas TPU kernels"),
]


def first_line(obj) -> str:
    # __doc__ directly, NOT inspect.getdoc: getdoc inherits the base-class
    # docstring, labeling every undocumented layer "Base class of all..."
    doc = getattr(obj, "__doc__", None) or ""
    line = doc.strip().splitlines()[0].strip() if doc.strip() else ""
    return line


def gen():
    out = ["# `bigdl_tpu` API index",
           "",
           "Generated by `tools/gen_api_docs.py` — public names per "
           "package with their first docstring line. See docs/MIGRATION.md "
           "for workflow-level mapping from the reference framework.",
           ""]
    import importlib
    for modname, blurb in SECTIONS:
        mod = importlib.import_module(modname)
        names = getattr(mod, "__all__", None) or \
            [n for n in dir(mod) if not n.startswith("_")]
        rows = []
        has_all = bool(getattr(mod, "__all__", None))
        for n in sorted(set(names)):
            obj = getattr(mod, n, None)
            if obj is None or inspect.ismodule(obj):
                continue
            if not has_all:
                # dir() fallback: keep only names defined inside the
                # package — no typing/__future__ imports in the index
                src = getattr(obj, "__module__", "")
                if not (src or "").startswith("bigdl_tpu"):
                    continue
            line = first_line(obj)
            if not line:
                # undocumented one-liner (e.g. _unary-generated): point at
                # the defining file, whose module docstring carries the
                # reference-parity citation
                src = getattr(obj, "__module__", "") or ""
                line = f"see `{src.replace('.', '/')}.py`" if src else ""
            line = line.replace("|", "\\|")
            if len(line) > 110:
                line = line[:107] + "..."
            rows.append(f"| `{n}` | {line} |")
        if not rows:
            continue
        out += [f"## {modname} — {blurb}", "",
                f"{len(rows)} public names.", "",
                "| name | summary |", "|---|---|"] + rows + [""]
    return "\n".join(out) + "\n"


if __name__ == "__main__":
    text = gen()
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "API.md")
    with open(path, "w") as f:
        f.write(text)
    print(f"wrote {path} ({len(text.splitlines())} lines)")
