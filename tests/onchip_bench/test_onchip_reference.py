"""The comparison that decides ``correct``, at a CPU size: the reference
computes what the program computes, the fp8 control reads wider gaps
than the program, and a run whose timed path is broken underneath comes
out not correct."""
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_CONFIG, TINY_MIX, FakeChip, add_cell
from onchip import harness
from onchip.model import load_arch

dense = load_arch("dense")


def _spec(**kw):
    base = dict(name="t", layers=2, d_model=64, heads=4, kv_heads=2,
                head_dim=16, d_ff=128, vocab=256, norm="rmsnorm",
                norm_eps=1e-6, mlp="swiglu", qk_norm=True, rope_theta=1e4,
                tied=True, dtype="float32", max_slots=2, block_size=16,
                max_seq_len=128, num_blocks=32)
    base.update(kw)
    return dense.Spec(**base)


@pytest.mark.parametrize("kw", [
    {},                                                   # qwen3's block
    {"norm": "layernorm", "mlp": "gelu", "qk_norm": False,
     "kv_heads": 1, "tied": False},                       # starcoder2's
], ids=["rmsnorm-swiglu-qknorm", "layernorm-gelu-untied"])
def test_reference_matches_the_programs_forward(kw):
    """In float32 the program's full forward and the reference agree:
    both follow the same published equations."""
    from repro.models.model import forward_full
    spec = _spec(**kw)
    params = dense.make_params(spec, 7)
    toks = np.random.default_rng(0).integers(0, spec.vocab, 37)
    want = np.asarray(forward_full(params, dense.program_config(spec),
                                   tokens=jnp.asarray(toks[None]))[0][0])
    ref = dense.Reference(spec, params)
    x = ref._hidden("f32", toks)[:len(toks)]
    got = np.asarray(dense._logits(spec, "f32", params["final_norm"],
                                   dense._head_w(params, spec), x))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the reference's own greedy continuation reads gaps of zero
    seq, served = list(toks), []
    for _ in range(4):
        x = ref._hidden("f32", np.asarray(seq))[len(seq) - 1:len(seq)]
        served.append(int(np.asarray(dense._logits(
            spec, "f32", params["final_norm"],
            dense._head_w(params, spec), x)).argmax()))
        seq.append(served[-1])
    g, _ = ref.gaps(toks, served)
    assert len(g) == 4 and np.all(g <= 1e-4)


def _tiny_run(root, seed, monkeypatch=None, fault=None, control=False):
    name = add_cell(root, "tinymix", TINY_CONFIG, TINY_MIX)
    cell = harness.load_cell(name, root)
    if fault is not None:
        fault(monkeypatch)
    return harness.run(cell, seed, 2.0, False, [FakeChip()], 0.0,
                       log=lambda s: None, control=control)


def test_control_reads_wider_gaps_than_the_program(bench_root):
    """At the tiny size the fp8 control's widest gap is several times the
    bf16 program's, on the same served tokens."""
    name = add_cell(bench_root, "tinymix", TINY_CONFIG, TINY_MIX)
    cell = harness.load_cell(name, bench_root)
    reqs = harness.traffic.generate(cell.mix, 11, 2.0, cell.spec.vocab,
                                    cell.spec.max_seq_len)
    srv = harness.setup(cell, 11, max(r.input_len for r in reqs))
    off = harness.offer(srv, reqs, cell.mix.ramp_s, 2.0,
                        cell.mix.drain_cap_s)
    cmp = harness.compare(cell, srv.params, harness.finished(reqs, off), 11,
                          control=True)
    assert cmp["tokens"] >= 64
    assert cmp["control_gap"] > 3 * cmp["gap"]
    assert cmp["control_gap"] > TINY_CONFIG["check"]["logit_gap_limit"]


def _frozen_state(mp):
    """A decode step that returns its state (the KV pool, positions)
    unchanged."""
    from repro.engine.engine import Engine
    orig = Engine._decode_dispatch_paged

    def step(self, params, cache, tokens, active, key):
        toks, _ = orig(self, params, cache, tokens, active, key)
        return toks, cache
    mp.setattr(Engine, "_decode_dispatch_paged", step)


def _half_batch(mp):
    """Half of each decode batch left out: rows past the middle get no
    model output (their logits are zero)."""
    from repro.engine import engine as E
    orig = E.sample

    def sample(logits, key, temperature):
        keep = jnp.arange(logits.shape[0]) < max(1, logits.shape[0] // 2)
        return orig(jnp.where(keep[:, None], logits, 0.0), key, temperature)
    mp.setattr(E, "sample", sample)


def _altered_token(mp):
    """A token altered where it is produced: every sampled id that is a
    multiple of 5 is moved to the next id."""
    from repro.engine import engine as E
    orig = E.sample

    def sample(logits, key, temperature):
        t = orig(logits, key, temperature)
        return jnp.where(t % 5 == 0, (t + 1) % logits.shape[-1], t)
    mp.setattr(E, "sample", sample)


@pytest.mark.parametrize("fault", [_frozen_state, _half_batch,
                                   _altered_token],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_a_broken_timed_path_is_not_correct(bench_root, monkeypatch, fault):
    out = _tiny_run(bench_root, 12, monkeypatch, fault)
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] \
        > out["checks"]["logit_gap"]["limit"]


def test_the_sound_tiny_run_is_correct(bench_root):
    """A sound run is correct; the calibration's control, read on the
    same run's sample, is past the limit."""
    out = _tiny_run(bench_root, 12, control=True)
    assert out["correct"] is True, out["checks"]
    assert out["control_gap"] > out["checks"]["logit_gap"]["limit"]
