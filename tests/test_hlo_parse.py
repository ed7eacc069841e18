"""HLO collective-byte parser unit tests (synthetic HLO lines + a real lowering)."""
import numpy as np
import pytest

from repro.utils.hlo import _sig_bytes, collective_bytes, op_histogram

HLO = """
HloModule jit_step
  %x = bf16[16,128]{1,0} parameter(0)
  %ar = bf16[16,128]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[64,128]{1,0} all-gather(%y), replica_groups=[2,4]<=[8], dimensions={0}
  %rs = f32[8,128]{1,0} reduce-scatter(%z), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = bf16[32,32]{1,0} collective-permute(%w), source_target_pairs={{0,1},{1,0}}
  %aa = s32[16,16]{1,0} all-to-all(%v), replica_groups={{0,1}}
  %done = bf16[4,4]{1,0} all-reduce-done(%h)
"""


def test_sig_bytes():
    assert _sig_bytes("bf16[16,128]{1,0}") == 16 * 128 * 2
    assert _sig_bytes("(f32[2,2], bf16[4])") == 16 + 8
    assert _sig_bytes("f32[]") == 4


def test_collective_bytes_semantics():
    out = collective_bytes(HLO)
    bk = out["bytes_by_kind"]
    assert bk["all-reduce"] == 16 * 128 * 2          # operand = output
    assert bk["all-gather"] == 64 * 128 * 4 / 4      # operand = output / group 4
    assert bk["reduce-scatter"] == 8 * 128 * 4 * 4   # operand = output * group 4
    assert bk["collective-permute"] == 32 * 32 * 2
    assert bk["all-to-all"] == 16 * 16 * 4
    assert out["counts"]["all-reduce"] == 1          # -done line not double counted
    assert out["total_bytes"] == sum(bk.values())


def test_real_lowering_collectives(subproc):
    """psum over 4 fake devices shows up as an all-reduce with the right bytes."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.utils.hlo import collective_bytes
mesh = Mesh(np.array(jax.devices()), ("d",))
def f(x):
    return jax.lax.psum(x, "d")
sh = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P(), check_vma=False)
txt = jax.jit(sh).lower(jax.ShapeDtypeStruct((8, 128), jnp.float32)).compile().as_text()
out = collective_bytes(txt)
assert out["counts"].get("all-reduce", 0) >= 1, out
assert out["total_bytes"] >= 2 * 128 * 4, out  # local shard operand bytes
print("HLO-OK", out["total_bytes"])
"""
    assert "HLO-OK" in subproc(code, n_devices=4)


def test_op_histogram():
    hist = dict(op_histogram(HLO))
    assert hist.get("all-reduce", 0) >= 1
