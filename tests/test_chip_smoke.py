"""chip_smoke.py on the CPU: the rehearsal size runs end to end, and the
plain command refuses to run without a TPU.

The script's real job — the full-width model on the chip — cannot be tested
here; these tests keep the script itself working between chip runs."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    # the script sits at the repo root and imports experiments.lm.data from
    # there, exactly as `python chip_smoke.py` finds them
    sys.path.insert(0, REPO)
    try:
        yield importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(REPO)


def test_rehearsal_passes_on_cpu(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearsal"]) == 0
    out = capsys.readouterr().out
    assert "proves nothing about the chip" in out
    result = json.loads(out.strip().splitlines()[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu", "count": 8}}
    # nothing on the path fell back to XLA, and all three phases reported
    for phase in ("[kernels]", "[train]", "[serve]"):
        assert phase in out


def test_plain_command_refuses_cpu_before_building_anything(
        chip_smoke, capsys, monkeypatch):
    def must_not_run(*args, **kwargs):
        raise AssertionError("a phase ran without a TPU")

    for phase in ("phase_kernels", "phase_train", "phase_serve"):
        monkeypatch.setattr(chip_smoke, phase, must_not_run)
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr().out
    assert "no TPU" in out and '"ok"' not in out


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """The driver also runs the script with nothing else of the repo beside
    it: it must exit non-zero and print no result line."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearsal"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, 2), proc.stdout + proc.stderr
    assert "ModuleNotFoundError" in proc.stderr
    assert '"ok"' not in proc.stdout


# two custom-call lines as XLA's TPU compiler prints them (operands and
# backend_config cut), and one line that is not a Mosaic call
_COMPILED = """
  %a.1 = (bf16[16,1024,128]{2,1,0}) custom-call(%b.10), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(one_step)/jvp(flash_attention_fwd)/pallas_call" stack_frame_id=8}, backend_config={}
  %c.1 = (bf16[8192,32000]{1,0}) custom-call(%d.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(one_step)/transpose(jvp(fused_ce_bwd))/pallas_call"}
  %e.1 = f32[8]{0} custom-call(%f), custom_call_target="Sharding", metadata={op_name="jit(one_step)/flash_decode_paged/x"}
"""


def test_mosaic_kernels_reads_compiled_text(chip_smoke):
    found = chip_smoke.mosaic_kernels(_COMPILED)
    assert sorted(found) == ["flash_attention_fwd", "fused_ce_bwd"]
    assert "[8192,32000]" in found["fused_ce_bwd"][0]
