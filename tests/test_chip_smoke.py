"""The first two chip rehearsals as tests: chip_smoke.py's own phases, run
here on the CPU at a tiny size (gpt3-tiny) — every check the chip run
makes, except the three only a TPU can meet (the flash kernel in the
program, a device that reports its peak memory, donation on). And the
entry points' refusal to run anywhere else."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def test_train_phase_tiny(capsys):
    out = chip_smoke.train_phase("gpt3-tiny", batch=2, seq=64)
    assert len(out["losses"]) == 7 and out["losses"][-1] < out["losses"][0]
    # the CPU backend takes no Pallas kernel and reports no memory stats:
    # main() fails a chip run on either
    assert out["tpu_custom_call"] is False
    assert out["peak_bytes_in_use"] is None
    assert _lines(capsys) == [dict(out, phase="train")]


def test_serve_phase_tiny(capsys):
    out = chip_smoke.serve_phase("gpt3-tiny", prompt_lens=(5, 12, 40, 100),
                                 new_tokens=8)
    assert out["tokens_returned"] == [8] * 7
    assert out["workload_compile_misses"] == 0
    assert out["max_slot_occupancy"] >= 4
    assert out["donate"] is False           # off on the CPU backend
    assert _lines(capsys) == [dict(out, phase="serve")]


def test_multichip_phases_on_virtual_devices(capsys):
    """The --multichip phases on four of conftest's virtual CPU devices:
    same mesh, sharding rules and per-shard attention as on four chips."""
    train = chip_smoke.multichip_train_phase("gpt3-tiny", batch=4, seq=64,
                                             devices=jax.devices()[:4])
    assert train["mesh"] == {"dp": 2, "tp": 2}
    assert train["device_ids"] == [0, 1, 2, 3]
    assert train["params_split_over_tp"] > 0 and not train["params_misplaced"]
    serve = chip_smoke.multichip_serve_phase("gpt3-tiny", replicas=4,
                                             new_tokens=8)
    assert len({r["device"] for r in serve["replicas"]}) == 4
    assert [ln["phase"] for ln in _lines(capsys)] == [
        "multichip_train", "multichip_serve"]


def test_a_failed_check_exits_nonzero():
    with pytest.raises(SystemExit) as e:
        chip_smoke.require(False, "the reason")
    assert e.value.code == "chip_smoke: FAILED: the reason"


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_entry_point_refuses_to_run_without_a_tpu(script):
    """No fallback: without a TPU the script exits non-zero, says why on
    stderr, and prints no result."""
    from _cpu_env import cpu_subprocess_env

    out = subprocess.run([sys.executable, os.path.join(REPO, script)],
                         capture_output=True, text=True, timeout=300,
                         cwd=REPO, env=cpu_subprocess_env())
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout and '"metric"' not in out.stdout
