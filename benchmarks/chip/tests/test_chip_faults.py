"""A run whose timed path is broken underneath reads ``correct: false``.

Each fault is planted in the sorted sweep's last step
(``ModelFamily.finalize_sorted``), which both the training round and the
serving engine run: a sweep that returns its state unchanged, one that
leaves half of its documents (training) or slots (serving) out, and one
that alters a token's topic where it is produced without counting it.
The cells run on one chip, so there is no exchange between chips to drop.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from chiptest import FakeChip, load_run


def unchanged(fam, orig):
    def finalize(cfg, local, e_grid, n_dk, tokens, mask):
        return orig(cfg, local, fam.encode(cfg, local), local.n_dk, tokens,
                    mask)
    return finalize


def half_batch(fam, orig):
    def finalize(cfg, local, e_grid, n_dk, tokens, mask):
        rows = jnp.arange(tokens.shape[0]) < tokens.shape[0] // 2
        e = jnp.where(rows[:, None], e_grid, fam.encode(cfg, local))
        return orig(cfg, local, e, jnp.where(rows[:, None], n_dk,
                                             local.n_dk), tokens, mask)
    return finalize


def token_altered(fam, orig):
    def finalize(cfg, local, e_grid, n_dk, tokens, mask):
        new, deltas = orig(cfg, local, e_grid, n_dk, tokens, mask)
        z = new.z.at[0, 0].set((new.z[0, 0] + 1) % cfg.n_topics)
        return new._replace(z=z), deltas
    return finalize


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "token_altered": token_altered}


@pytest.fixture
def fresh_traces():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", ["tiny_train", "tiny_serve"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_reads_incorrect(bench_copy, capsys, monkeypatch, fresh_traces,
                               cell, fault):
    from repro.core import family
    fam = family.get("lda")
    monkeypatch.setattr(fam, "finalize_sorted",
                        FAULTS[fault](fam, fam.finalize_sorted))
    run = load_run(bench_copy)
    monkeypatch.setattr(run, "setup_jax", lambda chips: [FakeChip()])
    rc = run.main(["--workload", cell, "--seed", "4", "--seconds", "1.5",
                   "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 0, err
    result = json.loads(out.strip().splitlines()[-1])
    assert result["correct"] is False, result["checks"]
