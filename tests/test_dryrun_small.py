"""Dry-run plumbing on a small virtual mesh (subprocess: needs >1 device).

Exercises plan_cell -> lower -> compile for each model family and all three
step kinds with reduced configs, on a (2 data x 2 model [+2 pod]) mesh."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
    import jax, jax.numpy as jnp
    from unittest import mock
    from repro.configs.base import SHAPES, ShapeConfig
    from repro.configs import registry
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import plan_cell
    from repro.utils.hlo import collective_bytes

    mesh = make_mesh({mesh_shape}, {mesh_axes})
    # shrink the configs + shapes so CPU compiles in seconds
    small = registry.smoke_config("{arch}").with_(name="{arch}", remat=False,
                                                  attn_chunk=0)
    SHAPES["train_4k"] = ShapeConfig("train_4k", 16, 8, "train")
    SHAPES["prefill_32k"] = ShapeConfig("prefill_32k", 32, 4, "prefill")
    SHAPES["decode_32k"] = ShapeConfig("decode_32k", 32, 8, "decode")
    SHAPES["long_500k"] = ShapeConfig("long_500k", 64, 2, "decode")
    with mock.patch.object(registry, "get_config", lambda n: small), \\
         mock.patch("repro.launch.steps.get_config", lambda n: small), \\
         mock.patch.dict("repro.launch.steps.TRAIN_MICROBATCH",
                         {{"{arch}": 4}}):
        plan = plan_cell("{arch}", "{shape}", mesh)
        lowered = plan.lower()
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        cb = collective_bytes(compiled.as_text())
        assert ma.argument_size_in_bytes > 0
        print("OK", "{arch}", "{shape}", plan.kind,
              "coll=", cb.get("total", 0))
""")


def _run(arch, shape, ndev=4, mesh_shape="(2, 2)", mesh_axes='("data", "model")'):
    code = CODE.format(arch=arch, shape=shape, ndev=ndev,
                       mesh_shape=mesh_shape, mesh_axes=mesh_axes)
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560, cwd=ROOT)
    assert r.returncode == 0 and "OK" in r.stdout, (r.stdout[-1500:] +
                                                    r.stderr[-3000:])


FAMILY_REPS = ["qwen3-14b", "deepseek-moe-16b", "rwkv6-3b", "hymba-1.5b",
               "whisper-small", "internvl2-26b"]


@pytest.mark.parametrize("arch", FAMILY_REPS)
def test_train_cell_small_mesh(arch):
    _run(arch, "train_4k")


@pytest.mark.parametrize("arch", ["qwen3-14b", "whisper-small", "rwkv6-3b"])
def test_prefill_cell_small_mesh(arch):
    _run(arch, "prefill_32k")


@pytest.mark.parametrize("arch", ["qwen3-14b", "deepseek-moe-16b",
                                  "hymba-1.5b", "whisper-small"])
def test_decode_cell_small_mesh(arch):
    _run(arch, "decode_32k")


def test_multipod_mesh_train():
    _run("qwen2-1.5b", "train_4k", ndev=8, mesh_shape="(2, 2, 2)",
         mesh_axes='("pod", "data", "model")')


def test_long500k_skips_full_attention():
    import jax
    from repro.configs.base import SHAPES
    from repro.configs.registry import get_config
    from repro.launch.steps import skip_reason
    assert skip_reason(get_config("llama3-405b"), SHAPES["long_500k"])
    assert skip_reason(get_config("rwkv6-3b"), SHAPES["long_500k"]) is None
    assert skip_reason(get_config("hymba-1.5b"), SHAPES["long_500k"]) is None
