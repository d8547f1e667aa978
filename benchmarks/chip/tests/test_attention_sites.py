"""The `attention_sites_lowered` reader on the lowering keys that
`drivers/train.py` records (`<kernel>[/gated]:<lowered|declined>:<source>`)."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run as R  # noqa: E402
READ = R.load_module(HERE.parent / "metrics" / "attention_sites_lowered.py",
                     "t_attention_sites_lowered").read


def test_counts_lowered_flash_attention_sites_both_directions():
    rec = {"lowering": {"flash_attention:lowered:cost": 4,
                        "flash_attention_bwd:lowered:cost": 2,
                        "flash_attention:declined:measured": 3,
                        "fused_mlp_swiglu:lowered:cost": 2,
                        "fused_mlp_bwd/gated:lowered:cost": 2}}
    assert READ(rec) == 6


def test_reads_nothing_where_no_attention_site_lowered():
    assert READ({}) is None
    assert READ({"lowering": {"fused_mlp_swiglu:lowered:cost": 2}}) is None
    assert READ({"lowering": {"flash_attention:declined:cost": 2}}) is None
