"""Each serve cell's check, with the timed path broken underneath, comes out
not correct; and each control fails the cell's limit.

The runs skip the harness's look for a chip and go through the rest of a
run (set-up, window, release, check) at the rehearsal sizes of the cells'
own configuration and traffic files, with the cells' own limits.  The
faults a cell can have: a step that returns its state unchanged, half of
the batch (or the PEs) left out, and a token or an answer altered where it
is produced.  No cell spans chips, so no exchange between chips can be
left out.
"""

import json
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import BENCH_DIR, Spec, make_run, run_cell

SPEC = Spec()


def prepare(spec, cell, seed, rehearse=True):
    """A serve cell's run from its files (the serve cells wait for their
    chip readings before ``BENCHMARK.json`` lists them)."""
    config, traffic = cell.split(".", 1)
    return make_run({"name": cell, "config": config, "traffic": traffic, "chips": 1},
                    json.loads((BENCH_DIR / "configs" / f"{config}.json").read_text()),
                    json.loads((BENCH_DIR / "traffic" / f"{traffic}.json").read_text()),
                    seed, rehearse)


def _run(cell, seed, factory=None, seconds=0.3):
    run = prepare(SPEC, cell, seed, rehearse=True)
    out = run_cell(SPEC, run, seconds, False, time.perf_counter(), driver_factory=factory)
    return out


# --------------------------------------------------------------------------
# serve cells
# --------------------------------------------------------------------------


def _serve_fault(fault):
    base = SPEC.driver("serve_decode").Driver

    class Faulty(base):
        def setup(self):
            super().setup()
            real, vocab = self.step, self.hf["vocab_size"]

            def step(params, cache, tokens, pos):
                if fault == "state_unchanged":
                    kept = jax.tree.map(jnp.copy, cache)
                    nxt, logits, _ = real(params, cache, tokens, pos)
                    return nxt, logits, kept
                nxt, logits, cache = real(params, cache, tokens, pos)
                if fault == "half_batch":
                    nxt = nxt.at[nxt.shape[0] // 2:].set(0)
                elif fault == "altered_token":
                    nxt = (nxt + 1) % vocab
                return nxt, logits, cache

            self.step = step

    return Faulty


SERVE_CELLS = ["stablelm-3b.chat-b16", "stablelm-3b.single-b1"]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_sound_run_is_correct(cell):
    out = _run(cell, 2**35 + 11)
    assert out["correct"], out["checks"]


# a batch of one has no half to leave out
SERVE_FAULTS = [
    (c, f) for c in SERVE_CELLS for f in ("state_unchanged", "half_batch", "altered_token")
    if not (f == "half_batch" and prepare(SPEC, c, 0, rehearse=True).traffic["batch"] < 2)
]


@pytest.mark.parametrize("cell,fault", SERVE_FAULTS)
def test_serve_fault_is_not_correct(cell, fault):
    out = _run(cell, 2**35 + 11, _serve_fault(fault))
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_serve_float8_control_fails_the_limit(cell):
    run = prepare(SPEC, cell, 2**33 + 7, rehearse=True)
    driver = SPEC.driver("serve_decode").Driver(run)
    driver.setup()
    rec = driver.window(0.3, lambda name: __import__("contextlib").nullcontext())
    driver.release()
    limit = run.traffic["limits"]["logit_gap"]
    assert driver.check(rec)["checks"]["logit_gap"]["value"] <= limit
    assert driver.control(rec)["logit_gap"] > limit




def test_serve_program_in_float32_serves_the_reference_argmax():
    """A witness that the reference and the program compute the same model:
    run in float32 at a small width (head_dim 64, 16 rotary dims), every
    token the program serves is the reference's best."""
    run = prepare(SPEC, "stablelm-3b.chat-b16", 2**34 + 1, rehearse=True)
    run.config.update(torch_dtype="float32", hidden_size=256, num_attention_heads=4,
                      num_key_value_heads=4, intermediate_size=512, num_hidden_layers=3,
                      vocab_size=1000)
    run.traffic.update(batch=3, max_seq=24, check_sessions=3)
    driver = SPEC.driver("serve_decode").Driver(run)
    driver.setup()
    rec = driver.window(0.5, lambda name: __import__("contextlib").nullcontext())
    driver.release()
    verdict = driver.check(rec)
    assert verdict["compared"] == 3 * 24  # every slot, every position
    assert verdict["checks"]["logit_gap"]["value"] < 1e-4
