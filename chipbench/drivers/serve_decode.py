"""Driver ``serve_decode``: closed-loop greedy decode through the program's
compiled decode step.

Set-up makes the weights on the device from the seed (the reference
module's generator, in the served dtype), builds the step with
``repro.serve.decode.make_serve_step`` and places and compiles it as
``repro.launch.serve.serve`` does (jit with the step's shardings, the cache
donated), then runs one step and one session reset as the warm-up.

The window is a static batch of sessions.  Each session starts from an
empty cache with one prompt token drawn from the seed, and greedy-decodes
through the cache up to ``max_seq - 1``; then every slot restarts.  The host
reads each step's tokens before it sends the next step, as a streaming
server does.  Host spans ``step``, ``readback`` and ``session_reset`` mark
what the host is doing, for the traced run.

The check: once the window has closed and the program's state is freed,
the reference runs over a sample of the served sessions (drawn from the
seed, the longest among them) and every served token's gap below the
reference's best logit is held to the cell's limit.
"""

from __future__ import annotations

import contextlib
import importlib
import time
from typing import Dict, List, Tuple

import numpy as np

# hf config key -> repro ModelConfig field
_HF_TO_PROGRAM = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "partial_rotary_factor": "rope_fraction",
    "rope_theta": "rope_theta",
    "use_qkv_bias": "qkv_bias",
    "tie_word_embeddings": "tie_embeddings",
    "use_parallel_residual": "parallel_block",
    "torch_dtype": "dtype",
}


def program_config(config: Dict):
    """The repro ModelConfig that runs ``config`` (its hf keys, mapped, plus
    the config's ``program`` fields)."""
    from repro.configs.base import ModelConfig

    kw = {field: config[key] for key, field in _HF_TO_PROGRAM.items()}
    kw["rope_theta"] = float(kw["rope_theta"])
    kw.update(config.get("program", {}))
    return ModelConfig(name=config["name"], **kw)


def to_program_params(w: Dict, params_sds) -> Dict:
    """Arrange the reference layout's arrays (no copies) as the program's
    parameter tree; refuses any leaf whose shape or dtype differs."""
    blocks = {
        "norm1": {"scale": w["ln1_scale"], "bias": w["ln1_bias"]},
        "norm2": {"scale": w["ln2_scale"], "bias": w["ln2_bias"]},
        "mixer": {k: {"w": w[k]} for k in ("wq", "wk", "wv", "wo")},
        "ffn": {"gate": {"w": w["w_gate"]}, "up": {"w": w["w_up"]},
                "down": {"w": w["w_down"]}},
    }
    tree = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm_scale"], "bias": w["final_norm_bias"]},
        "blocks": {"pos_0": blocks},
    }
    if "lm_head" in w:
        tree["lm_head"] = {"table": w["lm_head"]}
    import jax

    want = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), params_sds)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), tree)
    if want != got:
        raise ValueError(f"weights do not match the program's parameters: {got} vs {want}")
    return tree


class Driver:
    SPANS = ("step", "readback", "session_reset")

    def __init__(self, run):
        self.run = run  # chipbench.harness.CellRun
        self.hf = run.config
        self.traffic = run.traffic
        self.ref_mod = importlib.import_module(f"chipbench.reference.{run.config['reference']}")
        self.batch = int(self.traffic["batch"])
        self.max_seq = int(self.traffic["max_seq"])
        if self.traffic["prompt_tokens"] != 1:
            raise ValueError("serve_decode sessions take one prompt token")
        rng = np.random.default_rng(run.seed)
        # one prompt token per slot per session round; a round lasts
        # max_seq steps, so 1024 rounds outlast any window
        self.prompts = rng.integers(0, self.hf["vocab_size"], (1024, self.batch), np.int32)

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.launch.mesh import make_host_mesh
        from repro.serve.decode import init_cache, make_serve_step

        cfg = program_config(self.hf)
        mesh = make_host_mesh(data=1, model=1)
        with mesh:
            serve_fn, in_sh, out_sh, params_sds = make_serve_step(
                cfg, mesh, self.batch, self.max_seq)
        params_sh, cache_sh, self.tok_sh, self.pos_sh = in_sh
        self.gen = self.ref_mod.make_weights(self.hf)
        self.seed_words = self.ref_mod.seed_words(self.run.seed)
        params = to_program_params(self.gen(*self.seed_words), params_sds)
        self.params = jax.device_put(params, params_sh)
        with mesh:
            self.new_cache = jax.jit(
                lambda: init_cache(cfg, self.batch, self.max_seq), out_shardings=cache_sh)
            cache = self.new_cache()
            tok0 = jax.device_put(jnp.zeros((self.batch, 1), jnp.int32), self.tok_sh)
            pos0 = jax.device_put(jnp.zeros((self.batch,), jnp.int32), self.pos_sh)
            lowered = jax.jit(serve_fn, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(1,)).lower(self.params, cache, tok0, pos0)
            self.step = lowered.compile()
        self.step_module = self.step.as_text().split(",", 1)[0].split()[-1]
        # warm-up: a session reset and one step with readback, as in the window
        cache = self.new_cache()
        nxt, _, cache = self._step(cache, self.prompts[0][:, None], 0)
        np.asarray(nxt)
        del cache

    def _step(self, cache, tokens: np.ndarray, pos: int):
        import jax

        return self.step(
            self.params, cache, jax.device_put(tokens, self.tok_sh),
            jax.device_put(np.full((self.batch,), pos, np.int32), self.pos_sh))

    # ------------------------------------------------------------- window
    def window(self, seconds: float, span, traced=contextlib.nullcontext) -> Dict:
        """Decode until ``seconds`` have passed; the step in flight then is
        finished and counted."""
        max_steps = int(seconds * 2000) + self.max_seq
        served = np.zeros((max_steps, self.batch), np.int32)
        ends = np.zeros(max_steps)
        positions = np.zeros(max_steps, np.int32)
        rounds = np.zeros(max_steps, np.int32)
        n = 0
        rnd, pos, cache = -1, self.max_seq, None
        t0 = time.perf_counter()
        with traced():
            while True:
                if pos == self.max_seq:
                    with span("session_reset"):
                        rnd += 1
                        pos = 0
                        cache = None  # free the old cache before the new one is made
                        cache = self.new_cache()
                        tokens = self.prompts[rnd][:, None]
                with span("step"):
                    nxt, _, cache = self._step(cache, tokens, pos)
                with span("readback"):
                    tok = np.asarray(nxt)
                ends[n] = time.perf_counter()
                served[n], positions[n], rounds[n] = tok, pos, rnd
                n += 1
                tokens = tok[:, None]
                pos += 1
                if ends[n - 1] - t0 >= seconds:
                    break
        del cache
        return {
            "t0": t0, "t1": float(ends[n - 1]), "steps": n, "batch": self.batch,
            "ends": ends[:n], "positions": positions[:n], "rounds": rounds[:n],
            "served": served[:n], "max_seq": self.max_seq,
            "step_module": self.step_module, "config": self.hf,
        }

    def release(self) -> None:
        import jax

        for a in jax.tree.leaves(self.params):
            a.delete()
        del self.params, self.step, self.new_cache

    # -------------------------------------------------------------- check
    def sessions(self, rec: Dict) -> List[Tuple[int, int, np.ndarray]]:
        """The sample of served sessions to check, as (round, slot, tokens):
        drawn from the seed, the longest first, so that finished sessions
        come before the ones the window's end cut short.  ``tokens`` is the
        prompt token and then every token served in the session."""
        want = int(self.traffic["check_sessions"])
        rounds, served = rec["rounds"], rec["served"]
        rng = np.random.default_rng([self.run.seed, 1])
        pool = []  # (length, round, slot)
        for r in np.unique(rounds):
            length = int((rounds == r).sum())
            pool += [(length, int(r), int(s)) for s in rng.permutation(self.batch)]
        pool.sort(key=lambda x: -x[0])  # stable: seeded order within a length
        out = []
        for _, r, slot in pool[:want]:
            toks = np.concatenate([[self.prompts[r][slot]], served[rounds == r][:, slot]])
            out.append((r, slot, toks))
        return out

    def _gaps(self, rec: Dict, gap_fn) -> List[Tuple[int, np.ndarray]]:
        """(round, per-position gaps) of every sampled session, from
        ``gap_fn(weights, tokens)`` run on the sessions of each length."""
        w = self.gen(*self.seed_words)
        sample = self.sessions(rec)
        out = []
        for length in sorted({len(t) for _, _, t in sample}):
            group = [(r, t) for r, _, t in sample if len(t) == length]
            gaps = gap_fn(w, np.stack([t for _, t in group]))
            out += [(r, row) for (r, _), row in zip(group, gaps)]
        return out

    def check(self, rec: Dict) -> Dict:
        """Hold every sampled served token's gap to the cell's limit; a
        decode step fails when a token it served is over the limit."""
        limit = self.traffic["limits"]["logit_gap"]
        ref = self.ref_mod.make_reference(self.hf)
        rows = self._gaps(rec, lambda w, t: self.ref_mod.served_gaps(ref, w, t))
        worst = max(float(row.max()) for _, row in rows)
        failed = set()
        if limit is not None:
            for r, row in rows:
                failed |= {(r, int(p)) for p in np.flatnonzero(row > limit)}
        return {
            "checks": {"logit_gap": {"value": worst, "limit": limit}},
            "correct": limit is not None and worst <= limit,
            "attempted": int(rec["steps"]),
            "failed": len(failed),
            "compared": sum(row.size for _, row in rows),
        }

    def control(self, rec: Dict) -> Dict:
        """The control's reading on the same sessions: the gap of the token
        that the float8 forward puts first at each position."""
        ref = self.ref_mod.make_reference(self.hf)
        ctl = self.ref_mod.make_control(self.hf)
        rows = self._gaps(rec, lambda w, t: self.ref_mod.control_gaps(ref, ctl, w, t))
        return {"logit_gap": max(float(row.max()) for _, row in rows)}
