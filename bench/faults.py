"""Faults planted under the timed path, to show that ``correct`` catches
them: each patches a live session of a driver, never the repo's code."""
from __future__ import annotations

import jax


# -- training ----------------------------------------------------------

def train_unchanged(sess) -> None:
    """The step returns its state unchanged (its metrics as computed)."""
    inner = sess.trainer._jitted.__wrapped__
    sess.trainer._jitted = jax.jit(lambda s, b: (s, inner(s, b)[1]))


def train_half_batch(sess) -> None:
    """The step leaves out half of the batch: the mean over the rest."""
    inner = sess.trainer._jitted.__wrapped__

    def half(s, b):
        return inner(s, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
    sess.trainer._jitted = jax.jit(half, donate_argnums=(0,))


TRAIN = {"unchanged": train_unchanged, "half_batch": train_half_batch}


# -- serving -----------------------------------------------------------

def serve_token(sess, every: int = 7) -> None:
    """Every ``every``-th sampling of each engine returns each row's
    token plus one: a token altered where it is produced."""
    vocab = sess.cfg["vocab_size"]
    for loop in sess.group.loops:
        eng = loop.engine
        orig = eng._sample
        calls = [0]

        def bad(logits, temps, orig=orig, calls=calls):
            tok = orig(logits, temps)
            calls[0] += 1
            return (tok + 1) % vocab if calls[0] % every == 0 else tok
        eng._sample = bad


def serve_unchanged(sess) -> None:
    """The decode step returns the cache it was given: its state
    unchanged."""
    for loop in sess.group.loops:
        eng = loop.engine
        orig = eng._decode
        eng._decode = lambda p, c, b, orig=orig: (orig(p, c, b)[0], c)


SERVE = {"token": serve_token, "unchanged": serve_unchanged}
