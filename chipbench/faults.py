"""Faults a served cell can have, planted under the timed path: the check
that decides ``correct`` has to catch each. ``patch(owner, name, value)``
replaces an attribute (``setattr``, or pytest's ``monkeypatch.setattr``).

  python chipbench/calibrate.py readings --fault token_altered ...

reads a fault on the chip at a cell's own size; the CPU tests plant each
at a reduced size."""
from __future__ import annotations


def state_unchanged(patch) -> None:
    """The batched decode step returns the KV pool it was given."""
    from repro.serve.scheduler import ContinuousScheduler
    build = ContinuousScheduler._build_step

    def broken(self):
        step = build(self)

        def run(params, pool_k, pool_v, *rest):
            logits, _, _ = step(params, pool_k, pool_v, *rest)
            return logits, pool_k, pool_v
        return run
    patch(ContinuousScheduler, "_build_step", broken)


def half_batch(patch) -> None:
    """The batched step computes only the first half of its rows."""
    from repro.serve.scheduler import ContinuousScheduler
    build = ContinuousScheduler._build_step

    def broken(self):
        step, half = build(self), self.cfg.max_live // 2

        def run(*args):
            logits, pool_k, pool_v = step(*args)
            return logits.at[half:].set(0.0), pool_k, pool_v
        return run
    patch(ContinuousScheduler, "_build_step", broken)


def token_altered(patch) -> None:
    """Each request's fourth token is changed where it is sampled."""
    import jax.numpy as jnp
    from repro.serve.engine import Engine
    sample = Engine.sample_tokens

    def broken(self, logits, request_ids, step):
        out = sample(self, logits, request_ids, step)
        at = jnp.broadcast_to(jnp.asarray(step), out.shape) == 3
        return jnp.where(at, (out + 1) % logits.shape[-1], out)
    patch(Engine, "sample_tokens", broken)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
