"""``correct`` comes out false when the timed path is broken underneath, and
for the control: the reference in the program's place, its products in three
bfloat16 passes (the TPU's ``high``, one step below the configuration's
``highest``)."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import problems, serve, train
from bench.tests import tiny


def _train_false(name="burgers_xpinn_2x2.train"):
    r = tiny.execute(tiny.tiny_cell(name))
    assert r["correct"] is False, r["checks"]
    return r


def test_sound_run_is_correct():
    r = tiny.execute(tiny.tiny_cell("burgers_xpinn_2x2.train"))
    assert r["correct"] is True, r["checks"]


def test_control_is_not_correct(monkeypatch):
    def first(self):
        got = train.run_reference(self.cfg, self, self.chunk, "highest",
                                  dot="bf16x3")
        self.chunk_call()
        return dict(got, ok=True, good=self.chunk)

    monkeypatch.setattr(train.Program, "first", first)
    _train_false()


def test_state_left_unchanged(monkeypatch):
    from repro.core import ReferenceTrainer

    orig = ReferenceTrainer.run_chunk_guarded

    def stuck(self, state, batch, steps, lr_scale=None):
        keep = jax.tree.map(jnp.copy, state)
        _new, terms, health = orig(self, state, batch, steps, lr_scale)
        return keep, terms, health

    monkeypatch.setattr(ReferenceTrainer, "run_chunk_guarded", stuck)
    r = _train_false()
    assert r["checks"]["step_gap"]["value"] > 0.5


def test_half_the_batch_left_out(monkeypatch):
    orig = problems.pack_batch

    def half(data, neighbor, n_iface):
        b = orig(data, neighbor, n_iface)
        n = b["res_mask"].shape[1]
        b["res_mask"][:, n // 2:] = 0.0
        return b

    monkeypatch.setattr(problems, "pack_batch", half)
    r = _train_false()
    assert r["checks"]["loss_gap"]["value"] > r["checks"]["loss_gap"]["limit"]


def test_exchange_left_out(monkeypatch):
    import dataclasses

    orig = train.program_parts

    def parts(cfg):
        pde, decomp, model, dd = orig(cfg)
        return pde, decomp, model, dataclasses.replace(dd,
                                                       disable_exchange=True)

    monkeypatch.setattr(train, "program_parts", parts)
    _train_false()


def test_exchange_between_chips_left_out():
    """The four-chip cell on four host devices, sound and with the
    ppermute halo switched off."""
    code = """
import dataclasses, json
from bench import train
from bench.tests import tiny
sound = tiny.execute(tiny.tiny_cell("burgers_cpinn_4x1.train_4chip"))
orig = train.program_parts
def parts(cfg):
    pde, decomp, model, dd = orig(cfg)
    return pde, decomp, model, dataclasses.replace(dd, disable_exchange=True)
train.program_parts = parts
broken = tiny.execute(tiny.tiny_cell("burgers_cpinn_4x1.train_4chip"))
print(json.dumps([sound["correct"], broken["correct"], sound["device"]["count"]]))
"""
    out = tiny.run_four_devices(code).strip().splitlines()[-1]
    assert out == "[true, false, 4]"


def test_served_answer_altered(monkeypatch):
    from repro.serve import FieldEngine

    orig = FieldEngine.evaluate

    def altered(self, pts, order=2):
        out = orig(self, pts, order)
        out["u"] = out["u"] * np.float32(1.001)
        return out

    monkeypatch.setattr(FieldEngine, "evaluate", altered)
    r = tiny.execute(tiny.tiny_cell("usmap_heat_10.serve_steady"))
    assert r["correct"] is False, r["checks"]


def test_serve_control_is_not_correct(monkeypatch):
    from repro.serve import FieldEngine

    orig = FieldEngine.evaluate
    box = {}
    orig_init = serve.Server.__init__

    def init(self, cfg, traffic, seed):
        orig_init(self, cfg, traffic, seed)
        box["server"] = self

    def control(self, pts, order=2):
        out = orig(self, pts, order)
        s = box["server"]
        want = serve.ref_answers(s.cfg, s, np.asarray(pts), "highest",
                                 dot="bf16x3")
        return {k: want[k].astype(np.float32) for k in out}

    monkeypatch.setattr(serve.Server, "__init__", init)
    monkeypatch.setattr(FieldEngine, "evaluate", control)
    r = tiny.execute(tiny.tiny_cell("usmap_heat_10.serve_steady"))
    assert r["correct"] is False, r["checks"]
