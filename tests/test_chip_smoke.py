"""``chip_smoke.py`` off the chip: its phase function answers its
sessions on the CPU at a tiny size (the device assertion is patched
here, not through an option of the script), and the script itself
refuses to run without a TPU."""

import asyncio
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phase_answers_sessions_on_cpu(monkeypatch):
    import chip_smoke

    monkeypatch.setattr(chip_smoke, "device_checks", lambda engine: [])
    record = asyncio.run(chip_smoke.run_phase(
        "dense", model="tiny", quantization="", max_slots=4,
        max_seq_len=256, decode_chunk=4, prefill_buckets=(192, 256),
        short_sessions=3, long_sessions=1, short_chars=10, long_chars=60,
        max_tokens=8, session_limit_s=120.0,
    ))
    assert record["requests"] == 4
    assert 4 <= record["tokens_out"] <= 4 * 8
    assert record["variants"] > 0
    # three prompts inside the first bucket, one past it
    assert record["prompt_tokens"][0] <= 192 < record["prompt_tokens"][-1]


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    lines = done.stdout.strip().splitlines()
    assert not lines or json.loads(lines[-1]).get("ok") is not True
