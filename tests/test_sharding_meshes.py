"""Sharding/mesh tests. These spawn subprocesses because the forced host
device count must be set before jax initializes (and the main test process
keeps its single-device view)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.launch.analysis import CollectiveOp, parse_collectives, roofline_terms
from repro.parallel.rules import make_rules
from repro.parallel.spec import DEFAULT_RULES, Rules, partition_spec


# ---------------------------------------------------------------- specs
def test_partition_spec_basic():
    from jax.sharding import PartitionSpec as P

    r = DEFAULT_RULES
    assert partition_spec(("vocab", "embed"), r) == P("model")
    assert partition_spec(("layers", "embed", "mlp"), r) == P(None, None, "model")


def test_partition_spec_no_axis_reuse():
    from jax.sharding import PartitionSpec as P

    r = Rules.make(a="model", b="model", batch=("pod", "data"))
    # second use of "model" in one spec must be dropped
    assert partition_spec(("a", "b"), r) == P("model")
    assert partition_spec(("batch", "a"), r) == P(("pod", "data"), "model")


def test_make_rules_decode_kv_seq():
    from repro.configs import get_config

    cfg = get_config("granite-20b")  # MQA: kv=1 unshardable
    r = make_rules(cfg, "decode", global_batch=128, multi_pod=False)
    assert r.get("kv_seq") == "model"
    assert r.get("batch") == ("data",)
    r1 = make_rules(cfg, "decode", global_batch=1, multi_pod=True)
    assert r1.get("batch") is None
    assert r1.get("kv_seq") == ("data", "model")


def test_make_rules_seq_tp_vs_heads_tp():
    from repro.configs import get_config

    gem = make_rules(get_config("gemma3-4b"), "train", 256)
    assert gem.get("act_seq") == "model" and gem.get("heads") is None
    qwen = make_rules(get_config("codeqwen1.5-7b"), "train", 256)
    assert qwen.get("heads") == "model" and qwen.get("act_seq") is None


# ---------------------------------------------------------------- HLO parse
SAMPLE_HLO = """
  %ar = bf16[8,128]{1,0} all-reduce(bf16[8,128]{1,0} %x), replica_groups=[16,16]<=[256], to_apply=%add
  %ag.1 = f32[256,64]{1,0} all-gather(f32[16,64]{1,0} %y), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %rs = f32[4,32]{1,0} reduce-scatter(f32[16,32]{1,0} %z), replica_groups=[64,4]<=[256], dimensions={0}, to_apply=%add
  %cp = bf16[2,2]{1,0} collective-permute(bf16[2,2]{1,0} %w), source_target_pairs={{0,1}}
  %nothing = f32[2]{1,0} add(f32[2]{1,0} %a, f32[2]{1,0} %b)
"""


def test_parse_collectives_sample():
    ops = parse_collectives(SAMPLE_HLO)
    kinds = sorted(o.op for o in ops)
    assert kinds == ["all-gather", "all-reduce", "collective-permute", "reduce-scatter"]
    ar = next(o for o in ops if o.op == "all-reduce")
    assert ar.out_bytes == 8 * 128 * 2 and ar.group_size == 16
    assert ar.wire_bytes == pytest.approx(2 * ar.out_bytes * 15 / 16)
    ag = next(o for o in ops if o.op == "all-gather")
    assert ag.group_size == 4
    rs = next(o for o in ops if o.op == "reduce-scatter")
    assert rs.wire_bytes == pytest.approx(4 * 32 * 4 * 3)


def test_roofline_terms_bottleneck():
    t = roofline_terms(197e12, 100e9, 1e9, model_flops=197e12 * 256, n_chips=256)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["bottleneck"] == "compute"
    assert 0 < t["roofline_fraction"] <= 1.0 + 1e-9


# ---------------------------------------------------------------- mesh (subprocess)
_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
import jax, json
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced, SHAPES
from repro.train.step import make_train_bundle, make_serve_bundle
from repro.launch.mesh import make_test_mesh
import dataclasses

cfg = reduced(get_config("{arch}"))
mesh = make_test_mesh(data={data}, model={model}, pod={pod})
shape = dataclasses.replace(SHAPES["{shape}"], seq_len=64, global_batch=8)
from repro.parallel.rules import make_rules
rules = make_rules(cfg, shape.kind, shape.global_batch, multi_pod={multi_pod}, tp={model}, dp={data})
if shape.kind == "train":
    b = make_train_bundle(cfg, shape, mesh=mesh, multi_pod={multi_pod}, rules=rules)
else:
    b = make_serve_bundle(cfg, shape, mesh=mesh, multi_pod={multi_pod}, rules=rules)
named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P))
with mesh:
    compiled = jax.jit(b.fn, in_shardings=named(b.in_shardings),
                       out_shardings=named(b.out_shardings),
                       donate_argnums=b.donate_argnums).lower(*b.abstract_inputs).compile()
ca = compiled.cost_analysis()
print(json.dumps({{"ok": True, "flops": (ca or {{}}).get("flops", 0)}}))
"""


_REPO = pathlib.Path(__file__).resolve().parents[1]


def _run_mesh(arch, shape, data, model, pod=0, multi_pod=False):
    n = data * model * max(pod, 1)
    script = _MESH_SCRIPT.format(n=n, arch=arch, shape=shape, data=data,
                                 model=model, pod=pod, multi_pod=multi_pod)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, cwd=_REPO, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_small_mesh_train_compiles():
    out = _run_mesh("codeqwen1.5-7b", "train_4k", data=2, model=4)
    assert out["ok"]


@pytest.mark.slow
def test_small_multipod_mesh_train_compiles():
    out = _run_mesh("granite-moe-1b-a400m", "train_4k", data=2, model=2, pod=2,
                    multi_pod=True)
    assert out["ok"]


@pytest.mark.slow
def test_small_mesh_decode_compiles():
    out = _run_mesh("falcon-mamba-7b", "decode_32k", data=2, model=4)
    assert out["ok"]


_EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import LOCAL_CTX, ShardCtx, _moe_block_fn

# 16 experts, so that each of the 4 ranks holds 4 that receive tokens
cfg = reduced(get_config("granite-moe-1b-a400m")).replace(
    n_experts=16, top_k=4, dtype=jnp.float32)
E, D, F = cfg.n_experts_padded, cfg.d_model, cfg.d_ff
ks = jax.random.split(jax.random.PRNGKey(0), 5)
x = jax.random.normal(ks[0], (2, 32, D), jnp.float32)
mp = {"router": jax.random.normal(ks[1], (D, cfg.n_experts), jnp.float32),
      "w_in": jax.random.normal(ks[2], (E, D, F), jnp.float32) * D ** -0.5,
      "w_gate": jax.random.normal(ks[3], (E, D, F), jnp.float32) * D ** -0.5,
      "w_out": jax.random.normal(ks[4], (E, F, D), jnp.float32) * F ** -0.5}
y_ref = jax.jit(_moe_block_fn(cfg, LOCAL_CTX))(x, mp)
mesh = make_test_mesh(data=1, model=4)
with mesh:
    ep = jax.jit(_moe_block_fn(cfg, ShardCtx(mesh=mesh)))
    hlo = ep.lower(x, mp).compile().as_text()
    y_ep = ep(x, mp)
print(json.dumps({"err": float(jnp.max(jnp.abs(y_ep - y_ref))),
                  "scale": float(jnp.max(jnp.abs(y_ref))),
                  "all_reduce": "all-reduce" in hlo}))
"""


def test_expert_parallel_moe_matches_unmeshed():
    """The MoE layer under a mesh (``shard_map`` over 4 expert-parallel
    ranks, each computing its slice of experts, combined by a ``psum``)
    gives the unmeshed layer's output to float32 rounding."""
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", _EP_SCRIPT], capture_output=True,
                       text=True, cwd=_REPO, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["all_reduce"]
    assert out["scale"] > 0.1
    assert out["err"] < 1e-5 * out["scale"], out
