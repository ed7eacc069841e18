"""The collectives of the four-subdomain cPINN strip on four host devices:
what ``repro.obs.collective_counts()`` counts while the guarded chunk is
traced, and the scope the compiled guard's all-reduce carries."""


def test_strip_counts_halo_and_guard_collectives(subproc):
    out = subproc("""
import dataclasses, json
import numpy as np
from repro.core import CartesianDecomposition, DistributedDDTrainer
from repro.core import CPINN, Burgers1D, DDConfig, build_topology
from repro.core.nets import MLPConfig, SubdomainModelConfig
from repro.data import make_batch
from repro.obs import collective_counts

def delta(before, after):
    return {k: {f: v[f] - before.get(k, {}).get(f, 0) for f in v}
            for k, v in after.items()
            if v != before.get(k)}

pde = Burgers1D()
dec = CartesianDecomposition(((-1, 1), (0, 1)), 4, 1)
topo = build_topology(dec, 20)          # 20 points on each interface
model = SubdomainModelConfig(nets={"u": MLPConfig(2, 1, 20, 2)})
b = make_batch(dec, topo, pde, 32, 8, np.random.default_rng(0)).device_arrays()
out = {}
for name, off in (("sound", False), ("no_exchange", True)):
    tr = DistributedDDTrainer(pde, model, topo,
                              DDConfig(method=CPINN, residual_path="pallas",
                                       disable_exchange=off), lrs=1e-3)
    batch, state = tr.shard_batch(b), tr.shard_state(tr.init(0))
    before = collective_counts()
    state, terms, health = tr.run_chunk_guarded(state, batch, 1)
    assert bool(health["ok"]), health
    out[name] = delta(before, collective_counts())
    if name == "sound":
        hlo = tr._chunk_guarded.lower(
            tr.shard_state(tr.init(0)), batch, 1, np.ones(4, np.float32)
        ).compile().as_text()
        out["all_reduce_op_names"] = [
            ln.split('op_name="')[1].split('"')[0]
            for ln in hlo.splitlines() if " all-reduce(" in ln]
print(json.dumps(out))
""", n_devices=4)
    import json

    got = json.loads(out.strip().splitlines()[-1])
    # 2 edge colours x 2 payload leaves (u and the flux g.n), 20 float32 each
    assert got["sound"] == {
        "dd-comm-halo/collective-permute": {"ops": 4, "bytes": 4 * 20 * 4},
        "dd-comm-agree/all-reduce": {"ops": 1, "bytes": 4}}, got
    assert got["no_exchange"] == {
        "dd-comm-agree/all-reduce": {"ops": 1, "bytes": 4}}, got
    # the guard's per-step pmin is the one all-reduce inside the shard_map;
    # the other is the chunk's health reduction outside it
    agree = [n for n in got["all_reduce_op_names"] if "dd-comm-agree" in n]
    assert len(agree) == 1 and agree[0].endswith("dd-comm-agree/pmin"), got
