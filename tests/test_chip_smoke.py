"""chip_smoke.py cannot pass without a chip, and says why (tier-1, CPU).

The chip run itself is made through the chip tool; what a CPU can check is
the contract around it: no accelerator -> non-zero exit and no result line,
in bounded time; the parent process stays off jax; the compile cache is
placed from outside; a CPU number is never computed against an assumed
peak."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, timeout, **env):
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable, *code_or_args], cwd=REPO,
                          env=full_env, capture_output=True, text=True,
                          timeout=timeout)


def _printed_ok(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    try:
        return bool(lines) and json.loads(lines[-1]).get("ok") is True
    except (ValueError, AttributeError):
        return False


def test_chip_smoke_fails_fast_without_a_chip():
    proc = _run(["chip_smoke.py"], timeout=120)
    assert proc.returncode != 0
    assert not _printed_ok(proc.stdout)
    out = proc.stdout + proc.stderr
    assert "no TPU resource" in out      # train and serve: nothing to grant
    assert "no TPU: jax.devices() reports platform=cpu" in out  # rl
    assert "FAILED legs: ['train', 'serve', 'rl']" in out


def test_chip_smoke_tiny_runs_every_leg_and_still_fails():
    """--tiny walks the whole control flow on the CPU — every leg runs to
    its end and reports platform=cpu — and the exit code is still non-zero.
    The parent (this -c process) must end without jax imported."""
    code = ("import sys, chip_smoke\n"
            "rc = chip_smoke.main(['--tiny'])\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'\n"
            "sys.exit(rc)\n")
    proc = _run(["-c", code], timeout=420)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 1, out[-4000:]
    assert not _printed_ok(proc.stdout)
    for leg in ("train", "serve", "rl"):
        assert f"leg {leg}: done" in out, out[-4000:]
    assert "platform=cpu" in out
    assert "no TPU — legs ran on" in out


def test_result_line_has_exactly_the_contract_keys():
    """The driver refuses a last line with any other key in it."""
    sys.path.insert(0, REPO)
    import chip_smoke

    line = chip_smoke.result_line(
        {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
         "pid": 7, "jax": "0.9.0"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_compile_cache_placed_from_outside(tmp_path):
    code = ("from ray_tpu._private.jax_env import ensure_compile_cache\n"
            "import os\n"
            "d = ensure_compile_cache()\n"
            "assert os.environ['JAX_COMPILATION_CACHE_DIR'] == d\n"
            "print(d)\n")
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}

    def where(**env):
        return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                              env={**base, **env}, capture_output=True,
                              text=True, check=True, timeout=60
                              ).stdout.strip()

    # Unset: one fixed directory inside the checkout, the same from two
    # processes (the directory is part of the cache key).
    assert where() == where() == os.path.join(REPO, ".jax_cache")
    # Set: left alone.
    assert where(JAX_COMPILATION_CACHE_DIR=str(tmp_path)) == str(tmp_path)


def test_unknown_device_kind_has_no_peak():
    sys.path.insert(0, REPO)
    from benchmark.common import peak_for

    assert peak_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="mystery"):
        peak_for("mystery")
