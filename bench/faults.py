"""Faults planted under the timed path, to show that the check fails them.

Each breaks a built engine in place, after its step programs are warm:

* ``state_unchanged``: every step returns the serving state as the engine
  made it (the KV pools start at zero and stay there), so nothing a step
  writes is ever read back;
* ``half_batch``: a decode step gives the second half of its rows the
  first half's logits, as if half of the batch were left out;
* ``token_altered``: every fourth token is changed where it is emitted.

The exchange between chips does not exist on one chip.  The tests drive
a whole run with each at CPU size; ``bench/control.py --fault`` reads them
on the chip at a cell's own size.  Benchmark runs never plant them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def state_unchanged(engine) -> None:
    # Zeroing in place (donated) is the unchanged initial state and needs
    # no second copy of the pools: a product with 0 reads its operand, so
    # the output takes the donated buffer (``zeros_like`` did not, and ran
    # out of memory beside stablelm-1.6b's pools).
    zero = jax.jit(lambda s: jax.tree.map(lambda x: x * jnp.zeros(
        (), x.dtype), s), donate_argnums=0)
    for phase in ("prefill", "decode"):
        real = engine.engines[phase]

        def step(params, state, *rest, real=real):
            logits, new, cl = real(params, state, *rest)
            return logits, zero(new), cl

        step.stats = real.stats
        engine.engines[phase] = step


def half_batch(engine) -> None:
    real = engine.engines["decode"]

    def step(*args):
        logits, state, cl = real(*args)
        half = logits.shape[0] // 2
        if half:
            logits = logits.at[half:2 * half].set(logits[:half])
        return logits, state, cl

    step.stats = real.stats
    engine.engines["decode"] = step


def token_altered(engine) -> None:
    emit, count = engine._emit, [0]

    def altered(req, tok):
        count[0] += 1
        if count[0] % 4 == 0:
            tok = (tok + 1) % engine.cfg.vocab_size
        emit(req, tok)

    engine._emit = altered


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  token_altered)}
