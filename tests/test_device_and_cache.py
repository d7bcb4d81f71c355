"""Device selection and the compile-cache directory do what they are asked,
or say that they cannot — neither may quietly choose something else."""
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPlace:
    def test_tpu_place_without_a_tpu_raises(self):
        # the CPU tier has no TPU: running on the default backend instead
        # would be a silent change of device
        with pytest.raises(RuntimeError, match=r"Place\(tpu:0\) is not available"):
            paddle.TPUPlace(0).jax_device()
        with pytest.raises(RuntimeError, match="not available"):
            paddle.to_tensor([1.0], place=paddle.TPUPlace(0))

    def test_out_of_range_id_raises_rather_than_clamps(self):
        with pytest.raises(RuntimeError, match=r"Place\(tpu:9\)"):
            paddle.TPUPlace(9).jax_device()
        with pytest.raises(RuntimeError, match=r"Place\(cpu:99\) is not available"):
            paddle.core.place.Place("cpu", 99).jax_device()

    def test_cpu_place_is_the_host(self):
        assert paddle.CPUPlace().jax_device().platform == "cpu"


def _cache_dir_after_import(env_value):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "FLAGS_xla_persistent_cache_dir")}
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    env["PYTHONPATH"] = REPO
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, paddle_tpu; print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


class TestCompileCacheDir:
    def test_environment_variable_is_left_alone(self, tmp_path):
        want = str(tmp_path / "xla")
        assert _cache_dir_after_import(want) == want

    def test_unset_it_is_a_fixed_path_inside_the_checkout(self):
        assert _cache_dir_after_import(None) == os.path.join(REPO, ".jax_cache")
