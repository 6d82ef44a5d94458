"""chip_smoke.py's contract off the chip: without an accelerator it fails
and prints no result; alone in a directory it fails; and the explicit CPU
rehearsal runs every phase so the script cannot rot between chip runs."""
import json
import os
import shutil
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_REPO, "chip_smoke.py")


def _run(args, cwd=_REPO, script=_SCRIPT, timeout=600, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env.update(JAX_PLATFORMS="cpu", BIGDL_TPU_COMPILE_CACHE="0", **env_over)
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "ok" in doc:
            out.append(doc)
    return out


def test_no_accelerator_exits_nonzero_naming_the_platform():
    proc = _run([])
    assert proc.returncode != 0
    assert "platform is 'cpu'" in proc.stderr and "not 'tpu'" in proc.stderr
    # it names what it found, and prints no result line
    assert "platform=cpu" in proc.stdout
    assert _result_lines(proc.stdout) == []


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    lone = shutil.copy(_SCRIPT, tmp_path / "chip_smoke.py")
    proc = _run([], cwd=str(tmp_path), script=str(lone))
    assert proc.returncode != 0
    assert "cannot import bigdl_tpu" in proc.stderr
    assert _result_lines(proc.stdout) == []


def test_rehearsal_runs_every_phase_and_says_it_is_one():
    """Four virtual CPU devices: kernels (interpret mode), Optimizer ->
    DistriOptimizer replicated, Router -> DecodeScheduler, zero1, TP."""
    proc = _run(["--rehearse"],
                XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = [l for l in proc.stdout.splitlines()
            if l.startswith("chip_smoke:")]
    assert said and all("REHEARSAL" in l for l in said)
    phases = [json.loads(l)["phase"] for l in proc.stdout.splitlines()
              if l.startswith('{"phase"')]
    assert phases == ["kernels", "trainer", "server", "trainer_zero1",
                      "server_tp"]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"rehearsal": True, "ok": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 4}}
