"""Distribution tests: mesh building, sharding rules, a real multi-device
mini dry-run (subprocess with 8 host devices — XLA_FLAGS must be set
before jax imports, hence the isolation), elastic resharding."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import shardings as sh
from repro.launch.roofline import projected_memory_bytes
from repro.configs.shapes import SHAPES

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class TestShardingRules:
    def test_param_specs_cover_every_leaf(self):
        """Every arch's every param leaf gets a spec whose sharded dims
        divide (or GSPMD-pad) correctly — no rank mismatches."""
        for arch in configs.ALL_IDS:
            cfg = configs.get_reduced(arch)
            from repro.models.registry import build_model
            model = build_model(cfg)
            params = jax.eval_shape(
                lambda m=model: m.init(jax.random.PRNGKey(0)))
            flat, _ = jax.tree_util.tree_flatten_with_path(params)
            for path, leaf in flat:
                spec = sh.spec_for(cfg, path, leaf)
                assert len(spec) <= len(leaf.shape), \
                    f"{arch}: spec rank > leaf rank at {path}"

    def test_moe_expert_dim_sharded(self):
        cfg = configs.get_config("qwen3-moe-30b-a3b")
        from repro.models.registry import build_model
        params = jax.eval_shape(
            lambda: build_model(cfg.replace(n_layers=1)).init(
                jax.random.PRNGKey(0)))
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        found = 0
        for path, leaf in flat:
            name = sh._leaf_name(path)
            if name in ("w_gate", "w_up", "w_down") and \
                    leaf.shape[-3:-1].count(cfg.n_experts):
                pass
            if name == "w_gate" and cfg.n_experts in leaf.shape:
                spec = sh.spec_for(cfg, path, leaf)
                assert "model" in spec
                found += 1
        assert found >= 1

    def test_attention_tp_pattern(self):
        cfg = configs.get_config("qwen2.5-32b")
        wq = jax.ShapeDtypeStruct((cfg.d_model, 5120), "bfloat16")

        class K:  # fake path element
            key = "wq"
        assert sh.spec_for(cfg, (K(),), wq) == P(None, "model")
        K.key = "wo"
        assert sh.spec_for(cfg, (K(),), wq) == P("model", None)


class TestMiniDryrun:
    """Real 8-device compile of a reduced arch — the same code path as the
    512-device production dry-run, executed (not just compiled)."""

    @pytest.mark.parametrize("arch", ["gemma3-1b", "jamba-v0.1-52b",
                                      "rwkv6-3b"])
    def test_train_step_runs_on_8_devices(self, arch):
        out = run_subprocess(f"""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from repro import configs
            from repro.launch import shardings as sh
            from repro.models.registry import build_model
            from repro.optim.adamw import AdamW
            from repro.launch.mesh import make_host_mesh
            from repro.train.step import init_train_state, make_train_step
            mesh = make_host_mesh(model_parallel=2)
            cfg = configs.get_reduced("{arch}").replace(
                dtype="float32", vocab=64)
            model = build_model(cfg)
            opt = AdamW(lr=1e-3)
            state = init_train_state(model, cfg, opt, jax.random.PRNGKey(0))
            sshard = sh.param_shardings(cfg, mesh, state)
            state = jax.device_put(state, sshard)
            from jax.sharding import NamedSharding, PartitionSpec as P
            bshard = {{"tokens": NamedSharding(mesh, P("data", None)),
                      "labels": NamedSharding(mesh, P("data", None))}}
            rng = np.random.default_rng(0)
            batch = jax.device_put(
                {{"tokens": rng.integers(0, 64, (8, 32)).astype("int32"),
                 "labels": rng.integers(0, 64, (8, 32)).astype("int32")}},
                bshard)
            step = jax.jit(make_train_step(model, cfg, opt),
                           in_shardings=(sshard, bshard),
                           out_shardings=(sshard, None))
            l0 = None
            for i in range(3):
                state, metrics = step(state, batch)
                loss = float(metrics["loss"])
                assert np.isfinite(loss)
                l0 = l0 or loss
            assert loss < l0 + 1e-6
            print("OK", loss)
        """)
        assert "OK" in out


class TestElastic:
    def test_remesh_shapes(self):
        from repro.runtime.elastic import best_mesh_shape
        assert best_mesh_shape(512, 16) == (32, 16)
        assert best_mesh_shape(256, 16) == (16, 16)
        # losing 2 hosts of 16: 224 devices, TP 16 still divides
        assert best_mesh_shape(224, 16) == (14, 16)
        # TP no longer divides -> degrade TP
        assert best_mesh_shape(100, 16) == (25, 4)

    def test_checkpoint_reshard_roundtrip(self, tmp_path):
        """Save on one 'mesh', restore onto another (elastic downscale) —
        values identical (subprocess: 8 -> 4 devices)."""
        out = run_subprocess(f"""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import jax, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.checkpoint import io as ckpt_io
            mesh8 = jax.make_mesh((4, 2), ("data", "model"))
            w = np.arange(64, dtype=np.float32).reshape(8, 8)
            tree = {{"w": jax.device_put(
                w, NamedSharding(mesh8, P("data", "model")))}}
            ckpt_io.save("{tmp_path}/ck", tree, step=5)
            # elastic: restore onto a 4-device mesh
            devs = jax.devices()[:4]
            mesh4 = jax.sharding.Mesh(
                np.asarray(devs).reshape(2, 2), ("data", "model"))
            sharding = {{"w": NamedSharding(mesh4, P("data", "model"))}}
            restored, step = ckpt_io.restore("{tmp_path}/ck", tree,
                                             shardings=sharding)
            assert step == 5
            np.testing.assert_array_equal(np.asarray(restored["w"]), w)
            print("OK")
        """)
        assert "OK" in out


class TestRooflineAnalytics:
    def test_projected_memory_positive_and_ordered(self):
        for arch in ("qwen2.5-32b", "rwkv6-3b", "gemma3-1b"):
            cfg = configs.get_config(arch)
            vals = {}
            for name, shp in SHAPES.items():
                from repro.configs.shapes import cell_supported
                if not cell_supported(cfg, shp)[0]:
                    continue
                vals[name] = projected_memory_bytes(cfg, shp)
                assert vals[name] > 0
            # training moves more bytes than one decode step
            if "train_4k" in vals and "decode_32k" in vals:
                assert vals["train_4k"] > vals["decode_32k"]

    def test_gemma3_window_caps_decode_kv_read(self):
        cfg = configs.get_config("gemma3-1b")
        full = projected_memory_bytes(cfg.replace(sliding_window=None,
                                                  global_every=0),
                                      SHAPES["long_500k"])
        windowed = projected_memory_bytes(cfg, SHAPES["long_500k"])
        assert windowed < full * 0.5
