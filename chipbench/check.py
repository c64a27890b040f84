"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest, is run through the plain
float32 reference of the configuration's architecture module
(``chipbench/arch/<bench_arch>.py``) over each prompt and its served
tokens.  The number compared is the widest, over every served
token, of the logit gap ``|ref[c] - ref[t]|`` between the served token
``t`` and the reference's own choice ``c`` at that position: its
argmax (greedy traffic), or its draw with the request's own uniform
through the serving contract's sampler (temperature, top-p nucleus,
inverse CDF; sampled traffic).  The gap is 0 where they agree, and it
is the least uniform error in the reference's logits that would make
the reference choose the served token, so a near tie that rounding
flips reads small and a wrong token reads large.  The uniform of the
token at sequence index ``pos`` is the contract's counter-based draw,
``uniform(fold_in(fold_in(PRNGKey(0), sample_key), pos))``.

The control puts the reference, computed through float8 (``precision=
"fp8"``), in the program's place: at each position of the same prompts
and tokens it takes the token the control puts first (greedy) or draws
(sampled), and reads that token's number against the float32 reference.
"""

from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import traffic


def pick(submitted, seed: int, n: int) -> list:
    """``n`` finished requests: the longest (prompt + served tokens), one
    admitted through a prefix-cache hit where any was (its prompt was
    never chunked: the engine teacher-forced the suffix), and the rest
    drawn from the seed."""
    done = [s for s in submitted
            if s[1].done and len(s[1].generated) == s[0].max_new]
    if not done:
        return []
    rng = traffic.rng_for(seed, 7)
    longest = max(range(len(done)), key=lambda i: (
        len(done[i][0].prompt) + len(done[i][1].generated), -i))
    chosen = [longest]
    replayed = [i for i, s in enumerate(done)
                if s[1].chunk_pos == 0 and i != longest]
    if replayed and n > 1:
        chosen.append(int(rng.choice(replayed)))
    rest = [i for i in range(len(done)) if i not in chosen]
    chosen += list(rng.permutation(rest)[: max(n - len(chosen), 0)])
    return [done[i] for i in sorted(chosen)]


def uniforms(sample_key: int, positions) -> np.ndarray:
    base = jax.random.fold_in(jax.random.PRNGKey(0), sample_key)
    draw = jax.vmap(lambda p: jax.random.uniform(
        jax.random.fold_in(base, p), (), jnp.float32))
    return np.asarray(draw(jnp.asarray(positions, jnp.int32)), np.float64)


@functools.partial(jax.jit, static_argnames=("temperature", "top_p"))
def draw(logits, u, *, temperature: float, top_p: float):
    """The serving contract's sampler on ``logits`` (rows) with uniforms
    ``u``: temperature, the top-p nucleus, the inverse CDF."""
    lf = logits.astype(jnp.float32) / temperature
    order = jnp.argsort(-lf, axis=-1, stable=True)
    p = jax.nn.softmax(jnp.take_along_axis(lf, order, axis=-1), axis=-1)
    cum = jnp.cumsum(p, axis=-1)
    keep = (cum - p) < top_p
    kcum = jnp.cumsum(jnp.where(keep, p, 0.0), axis=-1)
    kcum = kcum / kcum[:, -1:]
    idx = jnp.minimum(jnp.sum(kcum <= u[:, None], axis=-1),
                      jnp.sum(keep, axis=-1) - 1)
    return jnp.take_along_axis(order, idx[:, None], -1)[:, 0]


def choose(logits, u, temperature: float, top_p: float):
    """The token ``logits`` put first (greedy) or draw with ``u``."""
    if temperature > 0:
        return draw(logits, jnp.asarray(u, jnp.float32),
                    temperature=temperature, top_p=top_p)
    return jnp.argmax(logits, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("temperature", "top_p"))
def _gaps(ref, tokens, u, *, temperature: float, top_p: float):
    want = choose(ref, u, temperature, top_p)
    at = lambda t: jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
    return jnp.abs(at(want) - at(tokens))


def gaps(ref, tokens, u, temperature: float, top_p: float) -> np.ndarray:
    """Each token's logit gap to the reference's own choice: the least
    uniform error in the reference's logits that explains the token."""
    return np.asarray(_gaps(ref, jnp.asarray(tokens, jnp.int32),
                            jnp.asarray(u, jnp.float32),
                            temperature=temperature, top_p=top_p),
                      np.float64)


def widest(cell, seed: int, served: List[tuple], temperature: float,
           top_p: float, *, precision: str = "f32") -> dict:
    """The cell's number over ``served`` = [(prompt, tokens, key)].
    ``precision="fp8"`` reads the control instead of the program."""
    import weights as W
    from harness import model_config

    from repro.models import Model

    arch = cell.arch
    cfg = model_config(cell.config)
    spec = arch.Spec.from_config(cell.config)
    w = W.make_weights(Model(cfg).abstract_params(), seed, cfg.d_model,
                       arch.init_std)
    worst, count = 0.0, 0
    for prompt, tokens, key in served:
        if not tokens:
            continue
        # every array below is padded to whole head blocks, so that
        # only a few shapes compile; the pads are cut before the max
        P, n = len(prompt), len(tokens)
        seq = prompt + tokens
        rows = np.arange(P - 1, P - 1 + n)
        ref = arch.logits_at(w, spec, seq, rows, whole_blocks=True)
        pad = ref.shape[0]
        u = (uniforms(key, P + np.arange(pad)) if temperature > 0
             else np.zeros(pad))
        served = np.zeros(pad, np.int32)
        served[:n] = tokens
        if precision != "f32":
            ctl = arch.logits_at(w, spec, seq, rows, precision=precision,
                                 whole_blocks=True)
            served = choose(ctl, jnp.asarray(u, jnp.float32), temperature,
                            top_p)
            del ctl
        d = gaps(ref, served, u, temperature, top_p)[:n]
        worst = max(worst, float(d.max()))
        count += n
    del w
    return {"widest": worst, "tokens": count,
            "kind": "logit_gap_to_reference_draw" if temperature > 0
            else "logit_gap_to_reference_argmax"}
