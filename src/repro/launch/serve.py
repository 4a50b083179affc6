"""Loopback launcher for the online inference service (DESIGN.md §14).

Spawns 1 inference-server process (``python -m repro.serve.server``) and
M concurrent client processes (``python -m repro.serve.client``) on
127.0.0.1, against a snapshot trained by a child process and persisted
through ``checkpoint.ckpt`` — the serving deployment shape in miniature: a
frozen model behind a socket, folded into by many concurrent users.

One process at a time holds the accelerator: the training child exits
before the server starts, the clients run on CPU (they only frame and
send requests), and the launcher itself touches no device until every
child has exited (DESIGN.md §11).

``--smoke`` is the CI end-to-end check: train a small LDA model, save
its Trainer snapshot, serve it from a separate process, fold the same
request corpus in from 2 concurrent client processes, and assert every
client's per-document result checksums equal the in-process
``FoldInEngine`` reference over the same snapshot.  Fold-in results are
a pure function of (snapshot, tokens, request seed) — so process
boundaries, request interleaving and batching composition must not move
a single bit.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any

from repro.launch.cache import enable_compile_cache


@dataclass
class ProcResult:
    """Exit status + captured output of one launched process."""
    name: str
    args: list[str]
    returncode: int
    stdout: str
    stderr: str
    result: dict[str, Any] | None = None  # parsed --out JSON, clients only


@dataclass
class ServeLaunchResult:
    address: str
    trainer: ProcResult | None = None
    server: ProcResult | None = None
    clients: list[ProcResult] = field(default_factory=list)
    server_stats: dict[str, Any] = field(default_factory=dict)

    def _procs(self) -> list[ProcResult]:
        return [p for p in (self.trainer, self.server) if p] + self.clients

    @property
    def ok(self) -> bool:
        return all(p.returncode == 0 for p in self._procs())

    def failures(self) -> list[ProcResult]:
        return [p for p in self._procs() if p.returncode != 0]


def _python() -> list[str]:
    return [sys.executable]


def _env(*, cpu: bool = False) -> dict[str, str]:
    """Child environment; ``cpu`` pins JAX to the host (the clients)."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    if cpu:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _tail(text: str, n: int = 15) -> list[str]:
    return (text or "").strip().splitlines()[-n:]


def _wait_address_file(path: str, proc: subprocess.Popen,
                       timeout: float) -> str:
    """Poll for the server's address file; fail fast if the server died."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"inference server exited early (code {proc.returncode}) "
                f"before publishing its address")
        if os.path.exists(path):
            try:
                with open(path) as f:
                    return list(json.load(f)["addresses"])[0]
            except (json.JSONDecodeError, KeyError, IndexError):
                pass  # torn read before os.replace — retry
        time.sleep(0.05)
    raise TimeoutError(f"server did not publish {path} within "
                       f"{timeout:.0f}s")


def _finish(proc: subprocess.Popen, name: str, args: list[str],
            timeout: float) -> ProcResult:
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        return ProcResult(name, args, returncode=-9,
                          stdout=out or "", stderr=(err or "")
                          + f"\n[launcher] killed after {timeout:.0f}s "
                            "timeout")
    return ProcResult(name, args, proc.returncode, out or "", err or "")


def _shutdown_server(address: str, timeout: float = 10.0
                     ) -> dict[str, Any]:
    """Fetch the server's STATS then tell it to stop — clients can't:
    none of them knows it is the last one out."""
    from repro.serve.client import InferenceClient
    stats: dict[str, Any] = {}
    try:
        with InferenceClient(address, timeout=timeout) as cli:
            try:
                stats = cli.stats()
            except Exception:
                pass
            cli.shutdown()
    except OSError:
        pass  # already down
    return stats


def train_snapshot(workdir: str, *, family: str, vocab_size: int,
                   n_topics: int, n_docs: int = 64, doc_len: int = 48,
                   n_rounds: int = 5, seed: int = 0) -> None:
    """Train a small model and persist its Trainer snapshot — the model
    the launched server process will freeze and serve.  Runs in the
    training child (``--train-snapshot``)."""
    import jax

    from repro.core import family as family_mod
    from repro.data.synthetic import CorpusConfig, make_topic_corpus
    from repro.engine.trainer import Trainer, TrainerConfig

    fam = family_mod.get(family)
    cfg = fam.config_cls(n_topics=n_topics, vocab_size=vocab_size)
    tokens, mask, _ = make_topic_corpus(CorpusConfig(
        n_topics=n_topics, vocab_size=vocab_size, n_docs=n_docs,
        doc_len=doc_len, seed=seed))
    tcfg = TrainerConfig(n_clients=1, snapshot_dir=workdir)
    trainer = Trainer(cfg, tokens, mask, config=tcfg,
                      key=jax.random.PRNGKey(seed))
    trainer.run(n_rounds, eval_every=n_rounds + 1)
    trainer.save_snapshot()


def _train_in_child(workdir: str, *, family: str, vocab_size: int,
                    n_topics: int, n_rounds: int, seed: int,
                    timeout: float) -> ProcResult:
    """Run :func:`train_snapshot` in a child process that exits before
    the server starts, so the two never hold the accelerator at once."""
    args = _python() + ["-m", "repro.launch.serve",
                        "--train-snapshot", workdir,
                        "--family", family,
                        "--vocab-size", str(vocab_size),
                        "--n-topics", str(n_topics),
                        "--train-rounds", str(n_rounds),
                        "--corpus-seed", str(seed)]
    proc = subprocess.Popen(args, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=_env())
    return _finish(proc, "trainer", args, timeout)


def launch_serve(*, family: str = "lda", vocab_size: int = 400,
                 n_topics: int = 8, n_clients: int = 2,
                 n_docs: int = 6, max_len: int = 48, max_slots: int = 8,
                 n_sweeps: int = 10, corpus_seed: int = 7,
                 seed_base: int = 1000, train_rounds: int = 5,
                 timeout: float = 420.0, workdir: str | None = None
                 ) -> tuple[ServeLaunchResult, Any]:
    """Train → snapshot → serve from a separate process → M concurrent
    client processes.  Returns (launch result, model config)."""
    from repro.core import family as family_mod

    cfg = family_mod.get(family).config_cls(n_topics=n_topics,
                                            vocab_size=vocab_size)
    own_dir = workdir is None
    tmp = tempfile.TemporaryDirectory() if own_dir else None
    workdir = tmp.name if own_dir else workdir
    try:
        trained = _train_in_child(workdir, family=family,
                                  vocab_size=vocab_size, n_topics=n_topics,
                                  n_rounds=train_rounds, seed=corpus_seed,
                                  timeout=timeout)
        result = ServeLaunchResult(address="", trainer=trained)
        if trained.returncode != 0:
            return result, cfg
        addr_file = os.path.join(workdir, "serve_addr.json")
        srv_args = _python() + ["-m", "repro.serve.server",
                                "--family", family,
                                "--vocab-size", str(vocab_size),
                                "--n-topics", str(n_topics),
                                "--snapshot-dir", workdir,
                                "--max-slots", str(max_slots),
                                "--max-len", str(max_len),
                                "--n-sweeps", str(n_sweeps),
                                "--address-file", addr_file]
        srv = subprocess.Popen(srv_args, stdout=subprocess.PIPE,
                               stderr=subprocess.PIPE, text=True,
                               env=_env())
        try:
            result.address = _wait_address_file(addr_file, srv,
                                                timeout=60.0)
        except (RuntimeError, TimeoutError):
            result.server = _finish(srv, "server", srv_args, timeout=5.0)
            return result, cfg

        client_procs = []
        for c in range(n_clients):
            out = os.path.join(workdir, f"client{c}.json")
            cargs = _python() + ["-m", "repro.serve.client",
                                 "--addr", result.address,
                                 "--client-id", str(c),
                                 "--n-docs", str(n_docs),
                                 "--vocab-size", str(vocab_size),
                                 "--max-len", str(max_len),
                                 "--corpus-seed", str(corpus_seed),
                                 "--seed-base", str(seed_base),
                                 "--out", out]
            client_procs.append(
                (subprocess.Popen(cargs, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True,
                                  env=_env(cpu=True)), cargs, out))
        for i, (proc, cargs, out) in enumerate(client_procs):
            pr = _finish(proc, f"client{i}", cargs, timeout)
            if pr.returncode == 0 and os.path.exists(out):
                with open(out) as f:
                    pr.result = json.load(f)
            result.clients.append(pr)
        result.server_stats = _shutdown_server(result.address)
        result.server = _finish(srv, "server", srv_args, timeout=30.0)
        return result, cfg
    finally:
        if tmp is not None:
            tmp.cleanup()


def _smoke(args) -> int:
    """CI serve smoke: 2 concurrent client processes over loopback must
    agree bit-for-bit with the in-process fold-in reference."""
    import tempfile as _tf

    with _tf.TemporaryDirectory() as workdir:
        result, cfg = launch_serve(
            family=args.family, vocab_size=args.vocab_size,
            n_topics=args.n_topics, n_clients=args.n_clients,
            n_docs=args.n_docs, max_len=args.max_len,
            max_slots=args.max_slots, n_sweeps=args.n_sweeps,
            corpus_seed=args.corpus_seed, seed_base=args.seed_base,
            train_rounds=args.train_rounds, timeout=args.timeout,
            workdir=workdir)
        if not result.ok:
            for p in result.failures():
                print(f"FAIL {p.name} rc={p.returncode}",
                      *_tail(p.stderr), sep="\n  ")
            return 1

        # In-process reference: same snapshot (via the same checkpoint
        # manifest), same requests, one engine — must be bit-identical
        # to what crossed the wire, regardless of batching.
        from repro.serve import snapshot as snapshot_mod
        from repro.serve.client import requests_for
        from repro.serve.engine import (FoldInEngine, ServeConfig,
                                        result_checksum)
        snap = snapshot_mod.from_checkpoint(workdir, cfg)
        eng = FoldInEngine(snap, ServeConfig(max_slots=args.max_slots,
                                             max_len=args.max_len,
                                             n_sweeps=args.n_sweeps))
        reqs = []
        for c in range(args.n_clients):
            reqs.extend(requests_for(
                c, vocab_size=args.vocab_size, n_docs=args.n_docs,
                max_len=args.max_len, corpus_seed=args.corpus_seed,
                seed_base=args.seed_base))
        ref = {str(uid): result_checksum(res)
               for uid, res in eng.run(reqs).items()}

        bad = 0
        for pr in result.clients:
            got = pr.result["checksums"]
            for uid, sha in got.items():
                if ref.get(uid) != sha:
                    print(f"MISMATCH {pr.name} uid={uid}: wire {sha[:12]} "
                          f"!= reference {str(ref.get(uid))[:12]}")
                    bad += 1
        total = sum(len(p.result["checksums"]) for p in result.clients)
        if bad or total != args.n_clients * args.n_docs:
            print(f"serve smoke FAILED: {bad} mismatches, "
                  f"{total} results")
            return 1
        stats = result.server_stats
        print(f"serve smoke OK: {total} docs over {args.n_clients} "
              f"concurrent clients bit-exact with in-process fold-in "
              f"(server p50 {stats.get('latency_p50_ms', 0):.1f} ms, "
              f"p99 {stats.get('latency_p99_ms', 0):.1f} ms, "
              f"shed {stats.get('shed', 0)})")
        return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="loopback launcher: 1 inference server x M "
                    "concurrent clients (repro.serve)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the CI checksum-parity smoke and exit")
    # BooleanOptionalAction so --no-reduced actually works (the seed
    # launcher's store_true+default=True flag could never be disabled).
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="small smoke geometry (--no-reduced serves a "
                         "larger model)")
    ap.add_argument("--family", default="lda")
    ap.add_argument("--n-clients", type=int, default=2)
    ap.add_argument("--n-docs", type=int, default=6)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--n-sweeps", type=int, default=10)
    ap.add_argument("--corpus-seed", type=int, default=7)
    ap.add_argument("--seed-base", type=int, default=1000)
    ap.add_argument("--train-rounds", type=int, default=5)
    ap.add_argument("--timeout", type=float, default=420.0)
    ap.add_argument("--train-snapshot", metavar="DIR", default=None,
                    help="(the launcher's training child) train and save "
                         "a snapshot into DIR, then exit")
    ap.add_argument("--vocab-size", type=int, default=None)
    ap.add_argument("--n-topics", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    vocab, topics = args.vocab_size, args.n_topics
    if args.reduced:
        args.vocab_size, args.n_topics, args.max_len = 400, 8, 48
    else:
        args.vocab_size, args.n_topics, args.max_len = 4096, 32, 128
    if args.train_snapshot:
        train_snapshot(args.train_snapshot, family=args.family,
                       vocab_size=vocab or args.vocab_size,
                       n_topics=topics or args.n_topics,
                       n_rounds=args.train_rounds, seed=args.corpus_seed)
        return 0

    if args.smoke:
        return _smoke(args)

    result, _cfg = launch_serve(
        family=args.family, vocab_size=args.vocab_size,
        n_topics=args.n_topics, n_clients=args.n_clients,
        n_docs=args.n_docs, max_len=args.max_len,
        max_slots=args.max_slots, n_sweeps=args.n_sweeps,
        corpus_seed=args.corpus_seed, seed_base=args.seed_base,
        train_rounds=args.train_rounds, timeout=args.timeout)
    if not result.ok:
        for p in result.failures():
            print(f"FAIL {p.name} rc={p.returncode}",
                  *_tail(p.stderr), sep="\n  ")
        return 1
    lats = [ms for p in result.clients for ms in p.result["latency_ms"]]
    lats.sort()
    total = sum(len(p.result["checksums"]) for p in result.clients)
    p50 = lats[len(lats) // 2] if lats else 0.0
    p99 = lats[min(len(lats) - 1, int(round(0.99 * (len(lats) - 1))))] \
        if lats else 0.0
    print(f"served {total} docs over {len(result.clients)} clients: "
          f"p50 {p50:.1f} ms, p99 {p99:.1f} ms "
          f"(server stats {json.dumps(result.server_stats)})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
