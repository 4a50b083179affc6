"""Plain reference for LDA's collapsed Gibbs round (Griffiths & Steyvers 2004).

Imports nothing of the program under test.  Each token's exact conditional
at the round's start, with its own count removed, is

    p_i(k) ∝ (n_dk − own + α) (n_wk − own + β) / (n_k − own + Vβ),

computed in float32 (``benchlib.gibbs`` reads the round against it).
``count_gap`` is the largest gap between the statistics the program keeps
(word-topic ``n_wk``, topic totals ``n_k``, document-topic ``n_dk``) and
the exact counts of its own assignments: 0 when every push landed once.

``control_round`` is this reference put in the program's place: an exact
Jacobi round (every token drawn from ``p_i``) whose arithmetic and kept
statistics are in ``dtype``.

Serving: ``fold_in`` is an exact collapsed-Gibbs fold-in against frozen
statistics, and ``serve_readings`` holds served documents against it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import gammaln

from benchlib import gibbs

LOCAL_STATE = ("z",)     # the per-token state a round moves
SHARED_STATE = ()        # round-start statistics not derived from it


def count_gap(model: dict, tokens, mask, kept: dict) -> float:
    """Largest |kept − counted| over n_wk, n_k and n_dk of ``kept["z"]``."""
    n_wk, n_dk = gibbs.counts(tokens, mask, kept["z"], model["vocab_size"],
                              model["n_topics"])
    return float(max(np.abs(np.asarray(kept["n_wk"]) - n_wk).max(),
                     np.abs(np.asarray(kept["n_k"]) - n_wk.sum(0)).max(),
                     np.abs(np.asarray(kept["n_dk"]) - n_dk).max()))


@partial(jax.jit, static_argnames=("alpha", "beta", "dtype"))
def _log_conditional(n_wk, n_k, n_dk, tok, mask, z, *, alpha, beta, dtype):
    """log p_i(k) for a block of documents: (Db, L, K)."""
    v, k = n_wk.shape
    own = (jnp.arange(k)[None, None, :] == z[..., None]) & mask[..., None]
    own = own.astype(dtype)
    doc = n_dk.astype(dtype)[:, None, :] - own + jnp.asarray(alpha, dtype)
    word = n_wk[tok].astype(dtype) - own + jnp.asarray(beta, dtype)
    tot = n_k.astype(dtype)[None, None, :] - own + jnp.asarray(beta * v, dtype)
    logit = jnp.log(doc) + jnp.log(word) - jnp.log(tot)
    return jax.nn.log_softmax(logit.astype(jnp.float32), axis=-1)


def _conditional(model, tokens, mask, z, dtype):
    n_wk, n_dk = gibbs.counts(tokens, mask, z, model["vocab_size"],
                              model["n_topics"])
    n_wk_d = jnp.asarray(n_wk, dtype)
    n_k_d = jnp.asarray(n_wk.sum(0), dtype)

    def log_conditional(tok, msk, zb, ndk):
        return _log_conditional(n_wk_d, n_k_d, jnp.asarray(ndk, dtype),
                                jnp.asarray(tok), jnp.asarray(msk),
                                jnp.asarray(zb), alpha=model["alpha"],
                                beta=model["beta"], dtype=dtype)
    return log_conditional, n_wk, n_dk


def round_readings(model: dict, tokens, mask, old: dict, new: dict) -> dict:
    """``gibbs_gap`` and ``stuck_docs`` of one round ``old → new``."""
    logc, _, n_dk = _conditional(model, tokens, mask, old["z"], jnp.float32)
    return gibbs.readings(logc, tokens, mask, old["z"], new["z"], n_dk)


def control_round(model: dict, tokens, mask, old: dict, seed: int, dtype
                  ) -> tuple[dict, dict]:
    """One exact Jacobi round from ``old`` in ``dtype``: returns (new
    state, the statistics it keeps)."""
    logc, n_wk, n_dk = _conditional(model, tokens, mask, old["z"], dtype)
    z_new = gibbs.draw(logc, tokens, mask, old["z"], seed, n_dk)
    m = mask.ravel()
    w = jnp.asarray(tokens.ravel()[m])
    d = jnp.asarray(np.repeat(np.arange(tokens.shape[0]),
                              tokens.shape[1])[m])
    zo, zn = jnp.asarray(old["z"].ravel()[m]), jnp.asarray(z_new.ravel()[m])
    kept_wk = gibbs.scatter_counts(jnp.asarray(n_wk), w, zn, zo, dtype=dtype)
    kept_dk = gibbs.scatter_counts(jnp.asarray(n_dk), d, zn, zo, dtype=dtype)
    kept = {"z": z_new,
            "n_wk": np.asarray(kept_wk.astype(jnp.float32)),
            "n_k": np.asarray(kept_wk.sum(0).astype(jnp.float32)),
            "n_dk": np.asarray(kept_dk.astype(jnp.float32))}
    return {"z": z_new}, kept


def consistent_kept(model: dict, tokens, mask, state: dict) -> dict:
    """The statistics a sound program keeps for assignments ``state``."""
    n_wk, n_dk = gibbs.counts(tokens, mask, state["z"], model["vocab_size"],
                              model["n_topics"])
    return {"z": state["z"], "n_wk": n_wk.astype(np.float32),
            "n_k": n_wk.sum(0).astype(np.float32),
            "n_dk": n_dk.astype(np.float32)}


# ---------------------------------------------------------------------------
# Serving: fold-in of new documents against frozen statistics
# ---------------------------------------------------------------------------

def frozen_stats(model: dict, tokens, mask, topics) -> dict:
    """The statistics of documents under their generating topics: what a
    trained model holds, made here from the seed."""
    n_wk, _ = gibbs.counts(tokens, mask, topics, model["vocab_size"],
                           model["n_topics"])
    return {"n_wk": n_wk.astype(np.float32),
            "n_k": n_wk.sum(0).astype(np.float32)}


@partial(jax.jit, static_argnames=("alpha", "n_sweeps", "dtype"))
def _fold_in(phi, tok, mask, key, *, alpha, n_sweeps, dtype):
    """Exact collapsed Gibbs fold-in of a (B, L) batch against frozen
    ``phi`` (V, K), scanning positions: returns (z, n_dk)."""
    b, l = tok.shape
    k = phi.shape[1]
    k_init, k_sweeps = jax.random.split(key)
    z = jnp.where(mask, jax.random.randint(k_init, (b, l), 0, k), 0)
    onehot = lambda t, m: ((jnp.arange(k)[None, :] == t[:, None])  # noqa
                           & m[:, None]).astype(dtype)
    n_dk = ((jnp.arange(k)[None, None, :] == z[..., None])
            & mask[..., None]).astype(dtype).sum(1)
    log_phi = jnp.log(phi.astype(dtype))

    def sweep(carry, kk):
        def pos(n_dk, inp):
            w, zo, m, kp = inp
            n_dk_m = n_dk - onehot(zo, m)
            logit = jnp.log(n_dk_m + jnp.asarray(alpha, dtype)) + log_phi[w]
            zn = jax.random.categorical(kp, logit.astype(jnp.float32))
            zn = jnp.where(m, zn, zo)
            return n_dk_m + onehot(zn, m), zn
        z, n_dk = carry
        n_dk, zt = jax.lax.scan(pos, n_dk, (tok.T, z.T, mask.T,
                                            jax.random.split(kk, l)))
        return (zt.T, n_dk), None

    (z, n_dk), _ = jax.lax.scan(sweep, (z, n_dk),
                                jax.random.split(k_sweeps, n_sweeps))
    return z, n_dk


def _pad(docs, l=None):
    """(tokens, mask) of ``docs`` padded to ``l``, by default the longest
    rounded up to 128, so that a sample of documents keeps one shape."""
    l = l or -(-max(len(d) for d in docs) // 128) * 128
    tok = np.zeros((len(docs), l), np.int32)
    mask = np.zeros((len(docs), l), bool)
    for i, d in enumerate(docs):
        tok[i, :len(d)], mask[i, :len(d)] = d, True
    return tok, mask


def _phi(model: dict, stats: dict, dtype):
    v = model["vocab_size"]
    return ((jnp.asarray(stats["n_wk"], dtype) + jnp.asarray(model["beta"], dtype))
            / (jnp.asarray(stats["n_k"], dtype)[None, :]
               + jnp.asarray(model["beta"] * v, dtype)))


def theta_of(model: dict, z, length: int, dtype=np.float32) -> np.ndarray:
    """Posterior-mean topic proportions of one document's assignments."""
    k, a = model["n_topics"], model["alpha"]
    n = np.bincount(np.asarray(z)[:length], minlength=k).astype(dtype)
    return (n + dtype(a)) / (dtype(length) + dtype(a * k))


def fold_in(model: dict, stats: dict, docs, seed: int, n_sweeps: int,
            dtype) -> list:
    """Exact fold-in of ``docs`` in ``dtype``: [(assignments, theta)]."""
    tok, mask = _pad(docs)
    z, _ = _fold_in(_phi(model, stats, dtype), jnp.asarray(tok),
                    jnp.asarray(mask), jax.random.PRNGKey(seed % 2**31),
                    alpha=model["alpha"], n_sweeps=n_sweeps, dtype=dtype)
    z = np.asarray(z)
    return [(z[i, :len(d)], theta_of(model, z[i], len(d), dtype))
            for i, d in enumerate(docs)]


@jax.jit
def _log_likelihood(phi, tok, mask, theta):
    pw = jnp.einsum("dlk,dk->dl", phi[tok], theta)
    return jnp.where(mask, jnp.log(jnp.maximum(pw, 1e-30)), 0.0).sum()


def perplexity(model: dict, stats: dict, docs, thetas) -> float:
    """exp(−mean log Σ_k θ_dk φ_wk) over every token of ``docs``."""
    tok, mask = _pad(docs)
    total = _log_likelihood(_phi(model, stats, jnp.float32), jnp.asarray(tok),
                            jnp.asarray(mask),
                            jnp.asarray(np.stack(thetas), jnp.float32))
    return float(np.exp(-float(total) / mask.sum()))


def log_joint(model: dict, stats: dict, docs, zs) -> float:
    """Σ_d log p(w_d, z_d | φ, α) of documents ``docs`` with assignments
    ``zs``, θ integrated out, in float64:

        Σ_i log φ_{w_i z_i} + log Γ(Kα) − log Γ(L + Kα)
                            + Σ_k [log Γ(n_k + α) − log Γ(α)]."""
    k, a, b = model["n_topics"], model["alpha"], model["beta"]
    n_wk, n_k = stats["n_wk"], np.asarray(stats["n_k"], np.float64)
    total = 0.0
    for w, z in zip(docs, zs):
        w, z = np.asarray(w, np.int64), np.asarray(z, np.int64)[:len(w)]
        phi = ((n_wk[w, z].astype(np.float64) + b)
               / (n_k[z] + b * model["vocab_size"]))
        n = np.bincount(z, minlength=k)
        total += (np.log(phi).sum() + gammaln(k * a) - gammaln(len(w) + k * a)
                  + (gammaln(n + a) - gammaln(a)).sum())
    return float(total)


def serve_readings(model: dict, stats: dict, served, *, seed: int,
                   n_sweeps: int) -> dict:
    """``served`` is [(tokens, assignments, theta)] of served documents.
    Against an exact collapsed-Gibbs fold-in of the same documents with
    as many sweeps (float32, ``highest``):

    * ``foldin_logp_gap``: |log joint of the served assignments − that of
      the exact fold-in's| per token (``log_joint``), in nats: the served
      chain reached what exact Gibbs sampling reaches;
    * ``theta_gap``: the largest |served θ − θ of the served assignments|;
    * ``foldin_ppl_ratio``: the served θ's perplexity over the exact
      fold-in's, the configuration's quality gate."""
    if not served:
        return {"foldin_logp_gap": float("inf"), "theta_gap": float("inf"),
                "foldin_ppl_ratio": float("inf")}
    docs = [np.asarray(t) for t, _, _ in served]
    gap = max(float(np.abs(np.asarray(th, np.float64)
                           - theta_of(model, z, len(t))).max())
              for t, z, th in served)
    with jax.default_matmul_precision("highest"):
        ref = fold_in(model, stats, docs, seed, n_sweeps, jnp.float32)
        ppl_ref = perplexity(model, stats, docs, [th for _, th in ref])
        ppl = perplexity(model, stats, docs, [th for _, _, th in served])
    logp = log_joint(model, stats, docs, [z for _, z, _ in served])
    logp_ref = log_joint(model, stats, docs, [z for z, _ in ref])
    n_tokens = sum(len(d) for d in docs)
    return {"foldin_logp_gap": abs(logp - logp_ref) / n_tokens,
            "theta_gap": gap, "foldin_ppl_ratio": ppl / ppl_ref}
