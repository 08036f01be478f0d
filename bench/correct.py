"""Decide ``correct``: served tokens against the plain reference.

After the window has closed and the program's state is freed, a sample
of the requests the window finished, drawn from the seed and always
holding the longest one, is run through the configuration's float32
reference, one forward pass over each prompt with its served tokens.
Each served token's reference logit is compared with the reference's
best logit at its position; the number compared is the widest such gap
over the sample (``logit_gap``), which is 0 where every served token is
the reference's own argmax. The traffic is greedy, so a served token
that lies far below the best is a wrong token, not a sampled one.

The control reads the same number for the reference computed in fp8
(the model file's ``Reference`` with ``fp8=True``): at each position of
the same prompts and served tokens, the gap of the token the fp8 forward
puts first.
"""
from __future__ import annotations

import numpy as np

# served tokens the sample should reach, and the most requests it takes
SAMPLE_TOKENS = 300
SAMPLE_MAX = 6


def sample(log, seed: int) -> list:
    """Finished requests of the run, drawn from the seed: the one with
    the most served tokens first, then others until the sample holds
    ``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX`` requests."""
    done = [r for r in log.records.values()
            if r.done_s is not None and r.failed is None and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.tokens), r.prompt_len, -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _sequence(log, rec, pad_to: int) -> tuple[np.ndarray, int]:
    prompt = log.prompts[rec.rid]
    seq = np.concatenate([prompt, np.asarray(rec.tokens[:-1], np.int32)])
    if len(seq) > pad_to:
        raise ValueError(f"request {rec.rid}: {len(seq)} tokens past the "
                         f"reference's {pad_to}")
    # causal attention: padding after the sequence changes nothing before
    # it, and one length means one compiled reference program
    return np.pad(seq, (0, pad_to - len(seq))), len(prompt)


def logit_gaps(log, recs: list, ref, pad_to: int,
               control=None) -> list[float]:
    """Per request, the widest gap by which a token lies below the
    reference's best logit at its position: the served token, or with
    ``control`` the token the control forward puts first."""
    out = []
    for rec in recs:
        seq, p = _sequence(log, rec, pad_to)
        n = len(rec.tokens)
        want = np.asarray(ref.logits(seq)[p - 1:p - 1 + n], np.float64)
        if control is None:
            toks = np.asarray(rec.tokens)
        else:
            toks = np.asarray(np.argmax(
                np.asarray(control.logits(seq)[p - 1:p - 1 + n]), -1))
        best = want.max(-1)
        got = want[np.arange(n), toks]
        out.append(float(np.max(best - got)))
    return out
