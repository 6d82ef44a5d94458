"""bench.py's surviving host-side logic: env knobs validate loudly, the
metrics dump speaks the bench schema, and the entry point measures on the
chip or fails — no cached line, no CPU stand-in, no ``*_failed`` line
with exit 0."""
import importlib
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


@pytest.fixture()
def bench_mod():
    import bench
    importlib.reload(bench)
    return bench


def test_variant_parser_validates(bench_mod, monkeypatch):
    b = bench_mod
    monkeypatch.delenv("BENCH_FUSED", raising=False)
    monkeypatch.delenv("BENCH_POOL_GRAD", raising=False)
    monkeypatch.delenv("BENCH_STEM", raising=False)
    assert b.resnet_bench_variant() == ("xla", "exact", "conv7")
    monkeypatch.setenv("BENCH_FUSED", "1")
    monkeypatch.setenv("BENCH_POOL_GRAD", "fast")
    monkeypatch.setenv("BENCH_STEM", "s2d")
    assert b.resnet_bench_variant() == ("pallas", "fast", "s2d")
    monkeypatch.setenv("BENCH_FUSED", "typo")
    with pytest.raises(SystemExit, match="BENCH_FUSED"):
        b.resnet_bench_variant()


def test_metrics_dump_written_from_lines(bench_mod, tmp_path, monkeypatch):
    b = bench_mod
    out = tmp_path / "BENCH_METRICS.json"
    monkeypatch.setenv("BENCH_METRICS_OUT", str(out))
    b._write_metrics_dump([
        {"metric": "resnet50_train_images_per_sec_per_chip", "value": 2436.9,
         "unit": "images/sec/chip", "vs_baseline": 40.6, "backend": "tpu"},
        {"metric": "bench_failed", "value": 0, "unit": "error"},
    ])
    dump = json.load(open(out))
    by = {l["metric"]: l for l in dump}
    assert by["bench/resnet50_train_images_per_sec_per_chip"]["value"] == \
        2436.9
    assert by["bench/resnet50_train_images_per_sec_per_chip"]["unit"] == \
        "images/sec/chip"
    assert by[
        "bench/resnet50_train_images_per_sec_per_chip/vs_baseline"
    ]["value"] == 40.6
    # every line speaks the bench schema
    assert all({"metric", "value", "unit"} <= set(l) for l in dump)


def test_metrics_dump_opt_out_and_never_raises(bench_mod, monkeypatch):
    b = bench_mod
    monkeypatch.setenv("BENCH_METRICS_OUT", "")
    b._write_metrics_dump([{"metric": "x", "value": 1, "unit": "u"}])  # no-op
    # unwritable path must not raise (the dump never fails the bench)
    monkeypatch.setenv("BENCH_METRICS_OUT", "/nonexistent_dir/x.json")
    b._write_metrics_dump([{"metric": "x", "value": 1, "unit": "u"}])


def test_bench_without_a_chip_exits_nonzero_and_prints_no_line():
    """`python bench.py` on the CPU platform must fail and say why: it
    may not print a cached, CPU or ``*_failed`` result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_METRICS_OUT="")
    proc = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=_REPO)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == ""


def test_failed_config_prints_its_line_and_exits_nonzero(bench_mod,
                                                         monkeypatch,
                                                         capsys):
    """A config that raises still leaves a ``*_failed`` line naming the
    platform, the other configs run, and the process exits non-zero."""
    b = bench_mod
    monkeypatch.setenv("BENCH_METRICS_OUT", "")
    monkeypatch.setenv("BIGDL_TPU_COMPILE_CACHE", "0")

    def fake_run(which):
        if which == "lenet":
            raise RuntimeError("boom")
        return {"metric": f"{which}_ok", "value": 1.0, "unit": "u",
                "backend": "cpu"}

    monkeypatch.setattr(b, "_run_config", fake_run)
    with pytest.raises(SystemExit) as ei:
        b.main(["--smoke", "--config", "lenet", "--config", "vgg"])
    assert ei.value.code not in (0, None)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert [l["metric"] for l in lines] == ["lenet_failed", "vgg_ok"]
    assert lines[0]["backend"] == "cpu" and "boom" in lines[0]["error"]
