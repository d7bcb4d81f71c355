"""What every family's ``build`` shares: the program's model is created in the
served dtype and made to hold the benchmark's seeded weights, leaf for leaf.
Which class is built, and which of its parameters a leaf is, is the family's
(``families/<family>.py``)."""
from __future__ import annotations

from contextlib import contextmanager


@contextmanager
def default_dtype(dtype: str):
    """Parameters are created in the served dtype at once: a float32 model
    first would hold twice the bytes on the chip for nothing."""
    import paddle_tpu as paddle

    before = paddle.get_default_dtype()
    paddle.set_default_dtype(dtype)
    try:
        yield
    finally:
        paddle.set_default_dtype(before)


def hold(net, weights: dict, state_key, name: str) -> dict:
    """Put each leaf of ``weights`` into the parameter ``state_key(leaf)``
    names. Returns ``{leaf: Parameter}``; a parameter that no leaf fills is
    an error."""
    state = net.state_dict()
    params = {}
    for leaf, arr in weights.items():
        p = state[state_key(leaf)]
        p.set_value(arr)
        params[leaf] = p
    if len(params) != len(state):
        raise ValueError(f"{name}: the model has {len(state)} leaves, "
                         f"the benchmark made {len(params)}")
    return params
