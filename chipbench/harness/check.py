"""What decides ``correct``: the served tokens against the plain
reference.

Once the window has closed and the program's state is freed, a sample
of the finished requests — drawn from the seed, the longest always in
it — is run through the reference as whole sequences (prompt, then the
served tokens), in float32.  At each position where the program served a
token, the gap is the reference's best logit minus the reference's
logit of the served token: zero where the program picked the
reference's choice, small where it picked a near-tie that rounding can
flip, large where the served token is wrong.  The number compared is the
widest gap over the sample.

The control puts the reference itself in the program's place at a
precision below the configuration's (``fp8``): at the same positions it
picks its own best token, and the float32 reference's gap for that token
is read the same way.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def sample(finished: Sequence, n: int, seed: int) -> List:
    """Up to ``n`` finished requests: the longest (prompt plus output)
    and the rest drawn by ``seed``."""
    if not finished:
        return []
    longest = max(finished, key=lambda s: (
        s.planned.prompt_len + s.planned.out_len, s.planned.rid))
    rest = [s for s in finished if s is not longest]
    rng = np.random.default_rng([seed, 4])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def sequences(picked: Sequence, length: int):
    """tokens (B, length): prompt then served tokens but the last, zero
    padded; for each request the positions whose logits chose its served
    tokens, and the served tokens."""
    toks = np.zeros((len(picked), length), np.int32)
    pos, served = [], []
    for b, s in enumerate(picked):
        prompt = np.asarray(s.req.prompt).reshape(-1)
        out = np.asarray(s.req.generated, np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        if seq.size > length:
            raise ValueError(f"request {s.planned.rid}: {seq.size} tokens "
                             f"exceed the reference length {length}")
        toks[b, :seq.size] = seq
        pos.append(np.arange(prompt.size - 1, prompt.size - 1 + out.size))
        served.append(out)
    return toks, pos, served


@jax.jit
def _gap_and_best(logits, tokens):
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, tokens[:, None], -1)[:, 0]
    return best - got, jnp.argmax(logits, -1)


def _rows(hidden, b, pos, width):
    idx = np.zeros(width, np.int32)
    idx[:pos.size] = pos
    return hidden[b][jnp.asarray(idx)]


def gaps(ref, picked: Sequence, length: int, width: int,
         precision: str = "f32", pick_own: bool = False) -> np.ndarray:
    """Gaps of the reference (float32) at every served position of the
    picked requests, concatenated.  With ``pick_own`` the tokens judged
    are those ``precision`` puts first, not the served ones."""
    toks, pos, served = sequences(picked, length)
    hid = ref.hidden(toks, "f32")
    own = ref.hidden(toks, precision) if pick_own else None
    out = []
    for b, (p, tok) in enumerate(zip(pos, served)):
        if p.size > width:
            raise ValueError(f"{p.size} served tokens exceed width {width}")
        if pick_own:
            _, tok = _gap_and_best(ref.logits(_rows(own, b, p, width),
                                              precision),
                                   jnp.zeros(width, jnp.int32))
            tok = np.asarray(tok)[:p.size]
        t = np.zeros(width, np.int32)
        t[:p.size] = tok
        g, _ = _gap_and_best(ref.logits(_rows(hid, b, p, width), "f32"),
                             jnp.asarray(t))
        out.append(np.asarray(g)[:p.size])
    return np.concatenate(out) if out else np.zeros(0)
