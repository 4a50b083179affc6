"""Fold-in serving engine: continuous batching of documents (DESIGN.md §14).

Online topic inference folds an unseen document into a *frozen* trained
model: the document gets its own assignment chain ``z`` and doc-topic
counts ``n_dk``, the shared statistics stay read-only, and after a fixed
number of local-only MHW sweeps the document's topic proportions are
harvested from ``n_dk``.  No pushes ⇒ no deltas, no barrier, no
projection conflicts — serving is embarrassingly parallel across
documents and across replicas of the snapshot.

The engine batches live documents into a slot grid ``(max_slots,
max_len)`` and runs ONE fused token-sorted sweep (the exact
``ModelFamily.sweep_sorted`` pipeline training uses — ``mhw.mix_chain``
semantics, tile-skipping sorted kernels) over every live slot per
:meth:`FoldInEngine.step`.  Slots are continuous: a document can be
admitted while its batch-mates are mid-chain, and harvested as soon as
its own chain has mixed ``n_sweeps`` sweeps.

**Determinism contract** (the serving analogue of the sorted-vs-scan
parity contract): a document's chain is a pure function of (snapshot,
tokens, request seed) — independent of which slots it happens to share
batches with.  The fused kernels make this possible because every
per-token MH step consumes explicit uniform streams in sorted-stream
order (``ops._step_uniforms``).  Per chunk, one compiled program
(:func:`_draw_uniforms`) draws every slot's streams at once: ``vmap`` of
``ops._step_uniforms`` over the slots' keys, each slot under its own
``fold_in(fold_in(PRNGKey(seed), sweep), chunk)`` key and at the
*single-document* layout width, then one gather routes each batched
sorted position to its slot's column through the slot's inverse
single-document order.  The draw width depends only on the chunk's
geometry, so it is the same for every slot and the streams stay on the
device.  The result is bit-identical to :func:`reference_fold_in` — the
Trainer's sorted sweep run on a one-document shard with its pushes
dropped — which is exactly what tests/test_serve_engine.py asserts per
family.

A folded-in document is not counted in the frozen statistics, so its
chain removes each token's own contribution from its doc row only
(``sweep_sorted(fold_in=True)``); removing it from ``n_wk`` as training
does would subtract a count that was never added.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import segment
from repro.kernels import ops
from repro.serve.snapshot import InferenceSnapshot

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine-side serving knobs (the service layer adds queueing on top).

    ``n_sweeps`` is the fold-in chain length: how many local-only sweeps
    a document mixes before harvest.  Fold-in converges fast — the
    training-time perplexity evaluators use 10 — so the default matches
    the eval convention.
    """

    max_slots: int = 8
    max_len: int = 256
    n_sweeps: int = 10


@dataclasses.dataclass(frozen=True)
class InferRequest:
    """One document to fold in.  ``seed`` fixes the request's chain: the
    same (snapshot, tokens, seed) triple always yields the same result,
    no matter how the request is batched or which replica serves it."""

    uid: int
    tokens: Sequence[int]
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class InferResult:
    uid: int
    theta: np.ndarray        # (K,) topic proportions
    assignments: np.ndarray  # (doc_len,) final topic per token
    n_sweeps: int


@dataclasses.dataclass
class _Slot:
    uid: int
    length: int
    age: int                 # completed sweeps


def _theta(prior: np.ndarray, n_dk_row: np.ndarray, length: int
           ) -> np.ndarray:
    """Posterior-mean topic proportions from a folded-in doc's counts."""
    return (n_dk_row + prior) / (float(length) + float(prior.sum()))


def result_checksum(res: InferResult) -> str:
    """Order-independent digest of one result — what the loopback smoke
    compares across client processes and the in-process reference."""
    h = hashlib.sha256()
    h.update(np.int64(res.uid).tobytes())
    h.update(np.ascontiguousarray(res.assignments, np.int32).tobytes())
    h.update(np.ascontiguousarray(res.theta, np.float32).tobytes())
    return h.hexdigest()


# Trace counts of the compiled draw, keyed by its signature: the
# compile-stability guard (steady-state serving must not grow them).
_UNIFORM_TRACES: dict[tuple[int, ...], int] = {}


@functools.partial(jax.jit, static_argnames=(
    "n_outcomes", "mh_steps", "width", "clen", "bp"))
def _draw_uniforms(keys: Array, ages: Array, live: Array, inv: Array,
                   order: Array, c: Array, *, n_outcomes: int,
                   mh_steps: int, width: int, clen: int, bp: int
                   ) -> tuple[Array, ...]:
    """Every slot's uniform streams for batched chunk ``c``, in the
    batched sorted order.

    keys (S, 2), ages (S,) and live (S,) describe the slots; ``inv``
    (S, clen) holds each slot's inverse single-document order of the
    chunk and ``order`` (S·clen,) the batched layout's.  Slot j draws
    ``ops._step_uniforms`` at the single-document ``width`` under
    ``fold_in(fold_in(key_j, age_j), c)``; batched position p takes
    column ``inv[j, t]`` of slot j for ``q = order[p]``, j = q // clen,
    t = q % clen.  Empty slots and the tail padding up to ``bp`` read
    0 (slot stream) and 0.5 (the four uniforms); their outputs are
    masked away."""
    sig = (keys.shape[0], n_outcomes, mh_steps, width, clen, bp)
    _UNIFORM_TRACES[sig] = _UNIFORM_TRACES.get(sig, 0) + 1

    def draw(key, age):
        ck = jax.random.fold_in(jax.random.fold_in(key, age), c)
        return ops._step_uniforms(ck, n_outcomes, mh_steps, width)

    slot = order // clen
    col = slot * width + inv[slot, order % clen]
    keep = live[slot]
    out = []
    for i, a in enumerate(jax.vmap(draw)(keys, ages)):   # (S, mh, width)
        fill = 0 if i == 0 else 0.5
        flat = jnp.swapaxes(a, 0, 1).reshape(mh_steps, -1)
        g = jnp.where(keep, flat[:, col], fill)
        out.append(jnp.pad(g, ((0, 0), (0, bp - order.shape[0])),
                           constant_values=fill))
    return tuple(out)


@jax.jit
def _admit_draw_state(keys: Array, inv: list[Array], j: Array, key: Array,
                      orders: list[Array]) -> tuple[Array, list[Array]]:
    """Slot ``j``'s rows of the draw state: its key and, per chunk, the
    inverse of its single-document sorted order."""
    return (keys.at[j].set(key),
            [x.at[j].set(jnp.argsort(o)) for x, o in zip(inv, orders)])


class FoldInEngine:
    """Slot-based continuous batching of fold-in chains over one frozen
    :class:`~repro.serve.snapshot.InferenceSnapshot`."""

    def __init__(self, snap: InferenceSnapshot,
                 scfg: ServeConfig | None = None):
        self.snap = snap
        self.scfg = scfg or ServeConfig()
        self.fam = snap.family
        self.cfg = snap.cfg
        s, l = self.scfg.max_slots, self.scfg.max_len
        self._tokens = jnp.zeros((s, l), jnp.int32)
        self._mask = jnp.zeros((s, l), bool)
        # Slot-grid local state; rows are rewritten wholesale at admit, so
        # the init values never reach a result.
        self._local, _ = self.fam.init_state(
            self.cfg, self._tokens, self._mask, jax.random.PRNGKey(0))
        self._slots: list[_Slot | None] = [None] * s
        self._layouts = None      # batched chunk layouts; rebuilt on change
        self._prior = np.asarray(snap.topic_prior(), np.float32)
        n_chunks = max(1, min(self.cfg.sorted_chunks, l))
        self._bounds = segment.chunk_bounds(l, n_chunks)
        clens = [e - b for b, e in zip(self._bounds, self._bounds[1:])]
        # Per-chunk width of a (1, max_len) document's sorted layout: the
        # chunk length padded to its batch tile.  A slot's streams are
        # drawn at this width, the same for every slot.
        tiles = [min(self.fam.sorted_tile_b(self.cfg), n) for n in clens]
        self._widths = tuple(-(-n // t) * t for n, t in zip(clens, tiles))
        # Device-side draw state: each slot's key and, per chunk, its
        # inverse single-document order; rows are rewritten at admit.
        key0 = jax.random.PRNGKey(0)
        self._keys = jnp.zeros((s,) + key0.shape, key0.dtype)
        self._inv = [jnp.zeros((s, n), jnp.int32) for n in clens]
        self._draw_sigs: set[tuple[int, ...]] = set()
        # Counters for the benchmark/service layer.
        self.sweeps_run = 0
        self.docs_admitted = 0
        self.docs_harvested = 0

    @property
    def uniform_traces(self) -> int:
        """Traces of the compiled draw over this engine's chunk shapes:
        one per distinct shape, never more as live sets, lengths and ages
        change.  The jit cache is shared, so another engine with equal
        shapes reuses the traces."""
        return sum(_UNIFORM_TRACES.get(sig, 0) for sig in self._draw_sigs)

    # ------------------------------------------------------------ occupancy
    @property
    def live(self) -> int:
        return sum(s is not None for s in self._slots)

    def free_slots(self) -> int:
        return sum(s is None for s in self._slots)

    # --------------------------------------------------------------- admit
    def admit(self, req: InferRequest) -> bool:
        """Pack a request into a free slot; False when the grid is full.

        Raises ``ValueError`` for an empty document, one longer than
        ``max_len``, or out-of-vocabulary token ids (the service layer
        maps this to a semantic ERROR frame, never a truncation)."""
        toks = np.asarray(req.tokens, np.int32).reshape(-1)
        if toks.size == 0:
            raise ValueError("empty document")
        if toks.size > self.scfg.max_len:
            raise ValueError(
                f"document has {toks.size} tokens, max_len is "
                f"{self.scfg.max_len}")
        if toks.min() < 0 or toks.max() >= self.cfg.vocab_size:
            raise ValueError("token id out of range for vocab_size "
                             f"{self.cfg.vocab_size}")
        try:
            j = self._slots.index(None)
        except ValueError:
            return False
        l = self.scfg.max_len
        row_tok = np.zeros((1, l), np.int32)
        row_tok[0, :toks.size] = toks
        row_mask = np.zeros((1, l), bool)
        row_mask[0, :toks.size] = True
        tok1 = jnp.asarray(row_tok)
        mask1 = jnp.asarray(row_mask)
        key = jax.random.PRNGKey(int(req.seed))
        # The slot's chain init IS the oracle's: family init on the
        # single-document shard, keyed by the request.
        local0, _ = self.fam.init_state(self.cfg, tok1, mask1, key)
        ld = self.fam.local_dict(self._local)
        for name, row in self.fam.local_dict(local0).items():
            ld[name] = ld[name].at[j].set(row[0])
        self._local = self.fam.local_from_dict(ld)
        self._tokens = self._tokens.at[j].set(tok1[0])
        self._mask = self._mask.at[j].set(mask1[0])
        # Single-doc sorted geometry per chunk: the inverse of these
        # orders routes the slot's uniform columns to flat positions.
        lays = self.fam.build_sorted_layouts(self.cfg, tok1, mask1)
        assert tuple(la.rows.shape[0] for la in lays) == self._widths
        self._keys, self._inv = _admit_draw_state(
            self._keys, self._inv, j, key, [la.order for la in lays])
        self._slots[j] = _Slot(uid=req.uid, length=int(toks.size), age=0)
        self._layouts = None
        self.docs_admitted += 1
        return True

    # ------------------------------------------------------------ uniforms
    def _chunk_uniforms(self, c: int, lay: segment.SortedLayout,
                        tile_b: int):
        """Per-request uniform streams for batched chunk ``c``: each live
        slot's streams are drawn under ITS single-doc geometry and key,
        mapped through its single-doc sorted order, then permuted into the
        batched sorted order — one compiled program, no host round trip.
        Empty slots get neutral values (their outputs are masked away)."""
        with jax.profiler.TraceAnnotation("repro.serve.uniforms",
                                          chunk=c):
            ages = np.asarray([0 if s is None else s.age
                               for s in self._slots], np.int32)
            live = np.asarray([s is not None for s in self._slots])
            static = dict(n_outcomes=self.fam.n_outcomes(self.cfg),
                          mh_steps=self.cfg.mh_steps, width=self._widths[c],
                          clen=self._bounds[c + 1] - self._bounds[c],
                          bp=lay.rows.shape[0])
            self._draw_sigs.add((len(self._slots),) + tuple(static.values()))
            return _draw_uniforms(self._keys, ages, live, self._inv[c],
                                  lay.order, c, **static)

    # ---------------------------------------------------------------- step
    def step(self) -> int:
        """One fused local-only sweep across every live slot.  Shared
        statistics are read-only; the returned deltas are dropped on the
        floor (fold-in never pushes).  Returns the number of live slots
        swept (0 = nothing to do)."""
        live = self.live
        if live == 0:
            return 0
        with jax.profiler.TraceAnnotation("repro.serve.step", live=live):
            if self._layouts is None:
                self._layouts = self.fam.build_sorted_layouts(
                    self.cfg, self._tokens, self._mask)
            local2, _deltas = self.fam.sweep_sorted(
                self.cfg, self._local, self.snap.shared, self.snap.tables,
                self.snap.stale, self._tokens, self._mask,
                jax.random.PRNGKey(0),  # unused: every chunk gets uniforms
                self._layouts, chunk_uniforms=self._chunk_uniforms,
                fold_in=True)
            self._local = self.fam.local_project(local2)
            for slot in self._slots:
                if slot is not None:
                    slot.age += 1
            self.sweeps_run += 1
        return live

    # ------------------------------------------------------------- harvest
    def harvest(self) -> list[InferResult]:
        """Free every slot whose chain has mixed ``n_sweeps`` sweeps and
        return its topic proportions + final assignments."""
        done = [j for j, slot in enumerate(self._slots)
                if slot is not None and slot.age >= self.scfg.n_sweeps]
        out = []
        with jax.profiler.TraceAnnotation("repro.serve.harvest",
                                          done=len(done)):
            ld = self.fam.local_dict(self._local)
            # The host waits here for the sweep's results on the device.
            with jax.profiler.TraceAnnotation("repro.serve.fetch"):
                n_dk = np.asarray(ld["n_dk"])
                z = np.asarray(ld["z"])
            for j in done:
                slot = self._slots[j]
                out.append(InferResult(
                    uid=slot.uid,
                    theta=_theta(self._prior, n_dk[j], slot.length),
                    assignments=z[j, :slot.length].copy(),
                    n_sweeps=slot.age))
                self._slots[j] = None
                self._mask = self._mask.at[j].set(False)
                self._layouts = None
                self.docs_harvested += 1
        return out

    # ----------------------------------------------------------------- run
    def run(self, requests: Iterable[InferRequest]
            ) -> dict[int, InferResult]:
        """Continuous-batching driver: admit as slots free up, sweep,
        harvest, until every request is served."""
        queue = list(requests)
        results: dict[int, InferResult] = {}
        while queue or self.live:
            while queue and self.admit(queue[0]):
                queue.pop(0)
            self.step()
            for res in self.harvest():
                results[res.uid] = res
        return results


# ---------------------------------------------------------------------------
# The oracle: fold-in through the Trainer path with pushes disabled
# ---------------------------------------------------------------------------

def reference_fold_in(snap: InferenceSnapshot, tokens: Sequence[int],
                      seed: int, *, n_sweeps: int,
                      max_len: int) -> tuple[Any, np.ndarray, np.ndarray]:
    """Fold one document in via the training code path: the sorted sweep
    Trainer runs (``ModelFamily.sweep_sorted``) on a one-document shard,
    with ``fold_in=True`` (the frozen statistics do not count the
    document) and its deltas dropped — i.e. pushes disabled.

    Returns ``(local_state, theta, assignments)``.  ``max_len`` must
    match the engine's slot width: chunk boundaries are derived from the
    padded length, so the geometry is part of the chain's identity.
    """
    fam, cfg = snap.family, snap.cfg
    toks = np.asarray(tokens, np.int32).reshape(-1)
    if toks.size > max_len:
        raise ValueError(f"document has {toks.size} tokens > {max_len}")
    row_tok = np.zeros((1, max_len), np.int32)
    row_tok[0, :toks.size] = toks
    row_mask = np.zeros((1, max_len), bool)
    row_mask[0, :toks.size] = True
    tok1, mask1 = jnp.asarray(row_tok), jnp.asarray(row_mask)
    key = jax.random.PRNGKey(int(seed))
    local, _ = fam.init_state(cfg, tok1, mask1, key)
    layouts = fam.build_sorted_layouts(cfg, tok1, mask1)
    for s in range(n_sweeps):
        local, _deltas = fam.sweep_sorted(
            cfg, local, snap.shared, snap.tables, snap.stale, tok1, mask1,
            jax.random.fold_in(key, s), layouts, fold_in=True)
        local = fam.local_project(local)
    n_dk = np.asarray(local.n_dk[0])
    prior = np.asarray(snap.topic_prior(), np.float32)
    theta = _theta(prior, n_dk, int(toks.size))
    z = np.asarray(local.z[0, :toks.size])
    return local, theta, z


# ---------------------------------------------------------------------------
# Fold-in quality: held-out perplexity of harvested proportions
# ---------------------------------------------------------------------------

def fold_in_perplexity(snap: InferenceSnapshot,
                       thetas: np.ndarray, tokens: np.ndarray,
                       mask: np.ndarray) -> float:
    """Held-out perplexity of documents under their *harvested* topic
    proportions and the frozen per-topic word distributions — the
    serving-side counterpart of ``family.perplexity`` (which folds in
    with its own internal chains).  The quality gate in
    tests/test_serve_engine.py compares the two."""
    phi = np.asarray(snap.language_model(), np.float32)  # (V, K)
    k = thetas.shape[1]
    pw = np.einsum("dk,dlk->dl", np.asarray(thetas, np.float32),
                   phi[np.asarray(tokens)][..., :k])
    m = np.asarray(mask, bool)
    logs = np.log(np.maximum(pw, 1e-30))[m]
    return float(np.exp(-logs.sum() / max(1, m.sum())))
