"""Roofline share of the Pallas flash-attention kernels (forward, dQ, dK/dV)
in a training step: the least time the chip could take for what each call
needs (``flops.flash_flops`` / ``flash_bytes``: causal pairs only, nothing
recomputed; the larger of FLOPs over the bf16 peak and bytes over the HBM
peak) summed over the calls in the traced window, over the summed device
time of those calls. The calls are the ``tpu_custom_call`` events of the
device's operation line; a call's kind and local shape are read from its
HLO line: the forward returns (out, lse), dQ one array, dK/dV two; the width
of one head, which splits a call's local width into heads, is the family's
``head_dim`` (a family that gives none has no such share). At these
shapes every call is compute-bound (the reader would say so otherwise by
raising on a memory-bound call it does not expect: see ``bound``)."""
import re

from benchmark import flops
from benchmark.trace import summary

_CALL = re.compile(r" = (\(?)((?:\w+\[[\d,]*\][^ ]*,? ?)+)\)? custom-call\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def classify(name: str):
    """(kind, batch, seq, width) of one flash custom call, or None."""
    if "tpu_custom_call" not in name:
        return None
    m = _CALL.search(name)
    if not m:
        return None
    outs = _SHAPE.findall(m.group(2))
    dims = [int(x) for x in outs[0][1].split(",")]
    if len(dims) != 3:
        return None
    if len(outs) == 1:
        kind = "dq"
    elif outs[1][0] == "f32":
        kind = "fwd"
    else:
        kind = "dkv"
    return kind, dims[0], dims[1], dims[2]


def read(run):
    width_of_head = getattr(run["family"], "head_dim", None)
    if run["trace"] is None or width_of_head is None:
        return None
    head_dim, peaks = width_of_head(run["config"]), run["peaks"]
    need = spent = 0.0
    for events in summary.device_ops(run["trace"]).values():
        for name, _, dur in events:
            c = classify(name)
            if c is None:
                continue
            kind, b, t, width = c
            sec, _ = flops.roofline_seconds(
                flops.flash_flops(b, width // head_dim, head_dim, t, kind),
                flops.flash_bytes(b, width // head_dim, head_dim, t, kind), peaks)
            need += sec
            spent += dur / 1e9
    if not spent:
        return None
    return flops.share(need, spent, "flash_roofline")
