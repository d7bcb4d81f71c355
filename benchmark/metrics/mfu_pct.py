"""Model FLOP/s utilization of a training cell: tokens/s/chip x the FLOPs a
token needs (forward + backward, causal attention counted once, nothing
recomputed: the family's ``train_flops_per_token``) over the chip's bf16 peak
from ``peaks.json``. End to end: idle time and every non-matmul op count
against it. A family that gives no such count has no MFU."""
from benchmark import flops


def read(run):
    rate = run["end_to_end"].get("tokens_per_s_chip")
    per_token = getattr(run["family"], "train_flops_per_token", None)
    if rate is None or per_token is None:
        return None
    need = rate * per_token(run["config"], run["seq"])
    return flops.share(need, run["peaks"]["bf16_flops_per_s"], "mfu_pct")
