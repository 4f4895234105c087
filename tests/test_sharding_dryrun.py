"""Distribution machinery on a small placeholder mesh (subprocess: the
dry-run proper uses 512 devices; here 8 devices validate the same code
paths quickly — sharding rules, lowering the ES step, HLO analysis)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs.registry import get_config
from repro.distributed.sharding import make_rules, dp_axes


class _FakeMesh:
    def __init__(self, names, sizes):
        self.axis_names = names
        self.shape = dict(zip(names, sizes))


def test_rules_single_vs_multi_pod():
    cfg = get_config("llama3-8b")
    single = dict(make_rules(cfg, _FakeMesh(("data", "model"), (16, 16))))
    multi = dict(make_rules(cfg, _FakeMesh(("pod", "data", "model"),
                                           (2, 16, 16))))
    assert single["batch"] == ("data",)
    assert multi["batch"] == ("pod", "data")
    assert single["heads"] == "model"
    # llama3 kv=8 < 16 -> replicated KV
    assert single["kv_heads"] is None
    # fsdp on -> param embed dim over DP axes
    assert multi["embed"] == ("pod", "data")


def test_rules_decode_shards_cache_seq_when_kv_replicated():
    cfg = get_config("qwen2-72b")
    rules = dict(make_rules(cfg, _FakeMesh(("data", "model"), (16, 16)),
                            kind="decode"))
    assert rules["cache_seq"] == "model"
    cfg2 = get_config("zamba2-2.7b")      # kv=32 shards over model
    rules2 = dict(make_rules(cfg2, _FakeMesh(("data", "model"), (16, 16)),
                             kind="decode"))
    assert rules2["kv_heads"] == "model"
    assert rules2["cache_seq"] is None


def test_rules_long_context():
    cfg = get_config("mamba2-780m")
    rules = dict(make_rules(cfg, _FakeMesh(("data", "model"), (16, 16)),
                            kind="long"))
    assert rules["batch"] is None          # batch=1
    assert rules["cache_seq"] == ("data",)


def test_rules_moe_modes():
    arctic = get_config("arctic-480b")
    grok = get_config("grok-1-314b")
    mesh = _FakeMesh(("data", "model"), (16, 16))
    r_a = dict(make_rules(arctic, mesh))
    r_g = dict(make_rules(grok, mesh))
    assert r_a["expert"] == "model" and r_a["moe_mlp"] is None      # EP
    assert r_g["expert"] is None and r_g["moe_mlp"] == "model"      # TP


@pytest.mark.slow
def test_mini_dryrun_8dev_subprocess():
    """Lower+compile the ES train step on a (2,4) placeholder mesh with a
    smoke config — the full 512-device dry-run machinery end to end."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import json
        import jax, jax.numpy as jnp
        from repro.configs.registry import get_smoke_config
        from repro.core.es_step import ESConfig, make_steps
        from repro.optim.adamw import OptConfig
        from repro.optim.schedule import get_schedule
        from repro.distributed.sharding import make_ctx
        from repro.launch.inputs import abstract_train_state
        from repro.launch.hlo_cost import analyze

        cfg = get_smoke_config("llama3-8b")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = make_ctx(cfg, mesh, "train")
        es = ESConfig(minibatch=4, n_train=64, seq_chunk=0)
        opt = OptConfig()
        steps = make_steps(cfg, es, opt, get_schedule("constant", 1), ctx)
        state_struct, state_sh = abstract_train_state(cfg, es, opt, 16, ctx)
        B, S = 16, 32
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                 "sample_ids": jax.ShapeDtypeStruct((B,), jnp.int32)}
        from jax.sharding import NamedSharding, PartitionSpec as P
        bsh = {"tokens": NamedSharding(mesh, P("data", None)),
               "labels": NamedSharding(mesh, P("data", None)),
               "sample_ids": NamedSharding(mesh, P("data"))}
        with mesh:
            lowered = jax.jit(steps["es_step"],
                              in_shardings=(state_sh, bsh),
                              out_shardings=(state_sh, None)).lower(
                                  state_struct, batch)
            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        res = analyze(compiled.as_text())
        assert res["flops"] > 0
        coll = sum(v["bytes"] for v in res["collectives"].values())
        assert coll > 0, "TP model must communicate"
        print("OK", json.dumps({"flops": res["flops"], "coll": coll}))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       cwd=str(Path(__file__).parent.parent))
    assert "OK" in r.stdout, r.stdout + "\n" + r.stderr
