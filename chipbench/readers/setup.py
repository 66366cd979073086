"""Readers of what set-up measured: its own length and the bare step."""

from chipbench import families, flops


def bare_step_ms(R):
    """Host clock over the bare steps of set-up, ending in block_until_ready."""
    return R.get("bare_step_s") and R["bare_step_s"] * 1e3


def step_mfu(R):
    """The family's FLOPs per token x tokens/s of the bare steps over the
    chip's published bf16 peak."""
    if not R.get("bare_step_s") or R.get("rehearsal"):
        return None
    family, sizes = families.of_file(R["config_file"])
    return flops.step_mfu_pct(family, sizes, R["bare_step_s"], R["device"]["kind"])


def setup_s(R):
    """``run.py``'s start to the window's opening, compile included."""
    return R.get("setup_s")
