"""Correctness of the §Perf optimization levers: every beyond-paper variant must be
numerically equivalent to the faithful path (debug-forward, never regress-silently)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


def test_causal_skip_attention_parity():
    """Python-loop causal block skipping == scanned masked attention."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(0, 1, (2, 64, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (2, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (2, 64, 2, 16)), jnp.float32)
    a = L.chunked_attention(q, k, v, causal=True, block_q=16, causal_skip=True)
    b = L.chunked_attention(q, k, v, causal=True, block_q=16, causal_skip=False)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


MOE_CODE = """
import os, dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model, make_batch
from repro.models.sharding import rules_for, use_rules

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg0 = get_config("deepseek-moe-16b").reduced(n_heads=4, n_kv_heads=4, vocab=512,
                                              n_experts=8, top_k=2, capacity_factor=8.0)
shape = ShapeConfig("t", 32, 4, "train")
batch = make_batch(cfg0, shape, "train")
outs = {}
for sm in (False, True):
    cfg = dataclasses.replace(cfg0, moe_shard_map=sm, dtype="float32")
    model = build_model(cfg)
    with jax.set_mesh(mesh), use_rules(rules_for()):
        params = model.init(jax.random.PRNGKey(0))
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    outs[sm] = (float(loss), grads)
l0, g0 = outs[False]; l1, g1 = outs[True]
assert abs(l0 - l1) < 5e-4 * max(1, abs(l0)), (l0, l1)
errs = [float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(g0), jax.tree.leaves(g1))]
assert max(errs) < 2e-3, max(errs)
print("MOE-SHARDMAP-PARITY-OK")
"""


@pytest.mark.slow
def test_moe_shardmap_parity(subproc):
    """shard_map expert parallelism == GSPMD grouped dispatch (loss AND grads)."""
    out = subproc(MOE_CODE, n_devices=8, timeout=900)
    assert "MOE-SHARDMAP-PARITY-OK" in out


SEQPAR_CODE = """
import numpy as np, jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import build_model, make_batch
from repro.models.sharding import rules_for, use_rules
import dataclasses

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = dataclasses.replace(get_config("llama3.2-1b").reduced(n_heads=4, n_kv_heads=4,
                                                            vocab=512), dtype="float32")
shape = ShapeConfig("t", 64, 4, "train")
batch = make_batch(cfg, shape, "train")
model = build_model(cfg)
outs = {}
for seqpar in (False, True):
    rules = rules_for()
    if seqpar:
        rules["res_seq"] = "model"
    with jax.set_mesh(mesh), use_rules(rules):
        params = model.init(jax.random.PRNGKey(0))
        loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, batch)
    outs[seqpar] = (float(loss), grads)
l0, g0 = outs[False]; l1, g1 = outs[True]
assert abs(l0 - l1) < 1e-4 * max(1, abs(l0)), (l0, l1)
errs = [float(jnp.max(jnp.abs(a - b))) for a, b in
        zip(jax.tree.leaves(g0), jax.tree.leaves(g1))]
assert max(errs) < 1e-3, max(errs)
print("SEQPAR-PARITY-OK")
"""


@pytest.mark.slow
def test_sequence_parallel_parity(subproc):
    """res_seq sharding changes layout only, never values."""
    out = subproc(SEQPAR_CODE, n_devices=8, timeout=900)
    assert "SEQPAR-PARITY-OK" in out
