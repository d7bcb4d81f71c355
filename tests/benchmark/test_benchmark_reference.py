"""The plain reference against the program at gpt_tiny size on the CPU, the
float8 control coming out as not correct, and a run with the timed path broken
underneath reporting ``correct: false``."""
import json

import numpy as np
import pytest

from benchmark import check, weights as W
from benchmark.manifest import Manifest
from benchmark.reference.common import diff_norm

F = Manifest().family("gpt")

CFG = dict(name="tiny", family="gpt", vocab_size=1024, hidden_size=128, num_layers=4,
           num_heads=4, head_dim=32, intermediate_size=512, max_position_embeddings=256,
           layer_norm_epsilon=1e-5, initializer_range=0.02, dtype="float32")
HP = dict(lr=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8, weight_decay=0.1)
SEED = 3_000_000_019
SPECS = F.leaf_specs(CFG)


def _ids(k):
    return np.random.default_rng(100 + k).integers(0, 1024, (2, 65))


def test_weights_are_seeded_and_leaves_repeat():
    a, b = W.make_weights(CFG, SEED, SPECS), W.make_weights(CFG, SEED, SPECS)
    other = W.make_weights(CFG, SEED + 1, SPECS)
    for i, (name, shape, kind) in enumerate(SPECS):
        assert tuple(a[name].shape) == tuple(shape)
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))
        assert not np.array_equal(np.asarray(a[name]), np.asarray(other[name]))
        assert np.array_equal(np.asarray(W.make_leaf(CFG, SEED, SPECS, i)), np.asarray(a[name]))
        mean = float(np.asarray(a[name], np.float32).mean())
        assert abs(mean - (1.0 if kind == "gain" else 0.0)) < 0.02


def _llama_fixture():
    """The second family of ``fixtures/`` (test_benchmark_manifest.py adds it
    to a copy of the benchmark) with its configuration."""
    from pathlib import Path

    from benchmark.manifest import _load

    fixtures = Path(__file__).resolve().parent / "fixtures"
    cfg = json.loads((fixtures / "configs" / "llama-tiny.json").read_text())
    return _load(fixtures / "families" / "llama.py", "benchmark_family_llama"), cfg


@pytest.mark.parametrize("family_and_cfg", [lambda: (F, CFG), _llama_fixture],
                         ids=["gpt", "llama-fixture"])
def test_logits_match_the_program(family_and_cfg):
    import paddle_tpu as paddle

    family, cfg = family_and_cfg()
    weights = lambda: W.make_weights(cfg, SEED, family.leaf_specs(cfg))
    model, params = family.build(cfg, weights())
    assert set(params) == {s[0] for s in family.leaf_specs(cfg)}
    model.eval()
    ids = _ids(0)[:, :-1]
    got = np.asarray(model(paddle.to_tensor(ids)).numpy())
    ref = np.asarray(family.forward_logits(cfg, weights(), ids))
    # float32 both sides, another order of summation: a few ulps of ~1
    assert np.abs(got - ref).max() < 5e-6
    # and the control's precision reads further off than that, by far
    ctl = np.asarray(family.forward_logits(cfg, weights(), ids, "fp8"))
    assert np.abs(ctl - ref).max() > 1e-3


@pytest.fixture(scope="module")
def trained():
    """Three steps of the program's compiled train step and of the reference."""
    import jax.numpy as jnp
    import paddle_tpu as paddle

    model, params = F.build(CFG, W.make_weights(CFG, SEED, SPECS))
    opt = paddle.optimizer.AdamW(
        learning_rate=HP["lr"], beta1=HP["beta1"], beta2=HP["beta2"],
        epsilon=HP["epsilon"], weight_decay=HP["weight_decay"],
        parameters=model.parameters())
    step = paddle.jit.compile_train_step(model, lambda m, a, b: m.loss(a, b), opt)
    ref = F.TrainReference(CFG, W.make_weights(CFG, SEED, SPECS), HP)
    ctl = F.TrainReference(CFG, W.make_weights(CFG, SEED, SPECS), HP, mode="fp8")
    program = {"loss": []}
    for k in range(3):
        ids = _ids(k)
        program["loss"].append(float(step(paddle.to_tensor(ids[:, :-1]),
                                          paddle.to_tensor(ids[:, 1:])).item()))
        ref.step(ids)
        ctl.step(ids)
        if k == 0:
            state = opt.state_dict()
            program["grad_norm"] = {
                leaf: float(jnp.linalg.norm(state[p.name + ".moment1"]._data))
                / (1 - HP["beta1"]) for leaf, p in params.items()}
    first = W.make_weights(CFG, SEED, SPECS)
    program["change_norm"] = {leaf: float(diff_norm(p._data, first[leaf]))
                              for leaf, p in params.items()}
    pack = lambda r: {"loss": r.losses, "grad_norm": r.grad_norms,
                      "change_norm": r.change_norms(lambda leaf: first[leaf])}
    return program, pack(ref), pack(ctl)


# float32 program against float32 reference at this size: rounding only
TINY_LIMITS = {"loss_gap": 1e-5, "grad_norm_gap": 1e-4, "change_norm_gap": 1e-3}


def test_train_step_matches_the_reference(trained):
    program, ref, _ = trained
    numbers = check.train_numbers(program, ref)
    assert check.judge(numbers, TINY_LIMITS, out=lambda m: None), numbers


def test_fp8_control_is_not_correct(trained):
    _, ref, ctl = trained
    numbers = check.train_numbers(ctl, ref)
    assert not check.judge(numbers, TINY_LIMITS, out=lambda m: None), numbers
    assert numbers["grad_norm_gap"][0] > 10 * TINY_LIMITS["grad_norm_gap"]


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    gap, leaf = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 0.0}, ref)
    assert leaf == "a" and gap == pytest.approx(0.1)   # c's 1e-9 is held to the median
    gap, leaf = check.worst_leaf_gap({"a": 1.0, "b": float("nan"), "c": 1e-9}, ref)
    assert leaf == "b" and gap != gap


def test_judge_needs_a_limit_for_every_number():
    lines = []
    assert not check.judge({"x": (0.0, "")}, {"y": 1.0}, out=lines.append)
    assert not check.judge({"x": (float("nan"), "")}, {"x": 1.0}, out=lines.append)
    assert check.judge({"x": (0.5, "here")}, {"x": 1.0}, out=lines.append)
    assert "x = 0.5 (limit 1, at here) ok" in lines[-1]


def test_served_gap_and_control():
    """Tokens the reference itself puts first trail its best by 0; an altered
    token, or the float8 control's choices, by more."""
    from benchmark import serve_job

    w = W.make_weights(CFG, SEED, SPECS)
    prompt = list(range(5, 45))
    ids = list(prompt)
    for _ in range(12):  # greedy continuation by the reference itself
        logits = np.asarray(F.forward_logits(CFG, w, np.asarray([ids])))[0, -1]
        ids.append(int(logits.argmax()))
    served = ids[len(prompt):]
    assert serve_job.served_gap(F, CFG, w, prompt, served, pad_to=32).max() == 0.0
    wrong = list(served)
    wrong[3] = (wrong[3] + 1) % 1024
    gaps = serve_job.served_gap(F, CFG, w, prompt, wrong, pad_to=32)
    assert gaps[3] > 0 and gaps.argmax() == 3
    ctl = serve_job.served_gap(F, CFG, w, prompt, served, mode="fp8", pad_to=32)
    assert ctl.shape == gaps.shape and (ctl >= 0).all()
