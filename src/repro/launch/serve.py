"""Batched serving driver: prefill a prompt batch, then decode (greedy by default).

``serve`` is the library entry (used by examples/serve_batch.py and the e2e
tests); ``main`` is the CLI.  Batching model: requests accumulate into fixed
batches (continuous batching is approximated by slot reuse at the example
level; the step functions themselves are batch-static, which is what the
decode dry-run cells lower).

Request-batch **admission** is a lock-table client
(:class:`BatchAdmission`): each concurrent batch slot is a lease in the
sharded asymmetric lock table, so admission control inherits the table's
guarantees — a crashed batch worker's slot expires after its TTL instead of
throttling the server forever, the fencing token identifies the admission for
downstream accounting, and the serving host (the table's local class) pays
zero simulated RDMA operations on its own admission path.  Off by default
(``admission_slots=0``) so library users and tests keep the bare fast path.
"""

from __future__ import annotations

import argparse
import functools
import threading
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs import ShapeConfig, get_config
from ..coord import CoordinationService, LeaseMode, RecoverableClient
from ..core import Overloaded
from ..data import SyntheticLMDataset
from ..models import Model, input_specs
from .compile_cache import use_compilation_cache
from .mesh import make_mesh
from .steps import build_decode_step, build_prefill_step


class BatchAdmission:
    """Admission control for request batches, as a lock-table client.

    Each of ``num_slots`` concurrent batch slots is a key in the sharded lock
    table; admitting a batch means taking a lease on a free slot.  The TTL is
    the worst-case batch walltime: a worker that dies mid-batch stops renewing
    and its slot re-opens at expiry, so capacity can never leak away.  The
    lease's fencing token travels with the batch for downstream accounting
    (e.g. a KV-cache pool can reject a zombie batch's writes).

    **Read slots vs write slots** (the mode-aware split): mutating batches
    (decode/prefill that write KV state) take EXCLUSIVE leases on the write
    slots as before, while read-only work — health probes, stats scrapes,
    cache-warm scans — shares ``read_slots`` *read lanes* through SHARED
    leases (:meth:`admit_read`): any number of readers join a lane with a
    single CAS and zero simulated RDMA ops on the serving host, so read
    traffic never queues behind (or consumes) batch capacity.  A maintenance
    operation that must quiesce a lane's readers takes an EXCLUSIVE lease on
    it (:meth:`quiesce`): the table's writer-intent barrier stops new joins,
    the cohort drains within one TTL, and readers resume the moment the
    maintenance lease is released.

    **Named workers and crash recovery**: a server thread that admits under a
    ``worker`` name goes through a ledgered
    :class:`~repro.coord.RecoverableClient`, so every slot admission leaves a
    durable trail.  When a worker thread dies mid-batch and its supervisor
    starts a replacement, the new thread calls :meth:`recover` with the same
    name: the predecessor's ledger replays and every still-valid slot lease
    is *reclaimed* via the fencing-checked CAS — the replacement resumes the
    batch (same slot, same fencing token) instead of waiting out the TTL or
    double-granting capacity.  Anonymous admissions (no ``worker``) keep the
    bare fast path.

    **Spans**: :meth:`admit`, :meth:`keepalive` and :meth:`complete` each
    write an ``admission.*`` span through :func:`repro.obs.start` and
    :func:`~repro.obs.end` (the lean form: they run cold between device
    steps) that carries the slot ``key``, the fencing ``token`` and the
    calling thread's lock-table operations over the call (``local_ops``,
    ``rdma_ops``, from its :class:`~repro.core.OpCounts`).  An admission
    that fails records a span with ``key`` and ``token`` None.
    """

    def __init__(self, num_slots: int = 4, ttl: float = 30.0,
                 svc: Optional[CoordinationService] = None,
                 read_slots: int = 0):
        if num_slots <= 0:
            raise ValueError("num_slots must be > 0")
        if read_slots < 0:
            raise ValueError("read_slots must be >= 0")
        # Single-host table by default: the serving host is the local class
        # for every shard, so admissions cost zero simulated RDMA ops.
        self.svc = svc or CoordinationService(
            num_hosts=1, num_shards=num_slots + read_slots)
        self.num_slots = num_slots
        self.read_slots = read_slots
        self.ttl = ttl
        self._tls = threading.local()
        # Ledgered clients by worker name (the identity that survives a
        # thread death).  A name is bound to one live thread at a time;
        # rebinding happens through recover().
        self._workers: Dict[str, RecoverableClient] = {}
        self._wlock = threading.Lock()
        # One async pipeline per server thread (PR 10): anonymous
        # keepalives ride it, and the table's hedged probes from that
        # thread share its flush postings.  Kept in a list too, so
        # stats() can aggregate across threads.
        self._pipes = []
        #: EXCLUSIVE admissions refused at the gate by the overload layer.
        self.sheds = 0

    def _proc(self):
        # One coordination Process per server thread: the MCS queue keys its
        # descriptors by pid, so sharing one pid across threads would corrupt
        # the shard ALocks (service.host_process: "call once per host thread").
        p = getattr(self._tls, "p", None)
        if p is None:
            p = self._tls.p = self.svc.host_process(0)
        return p

    def _pipe(self):
        # This thread's AsyncClient over the admission table.  On the
        # default single-host table every op is home-class and resolves
        # inline (identical semantics, zero RDMA); over a multi-host
        # service, remote keepalives coalesce into one posting per flush.
        pl = getattr(self._tls, "pipe", None)
        if pl is None:
            pl = self._tls.pipe = self.svc.async_client(self._proc())
            with self._wlock:
                self._pipes.append(pl)
        return pl

    def _worker(self, worker: str) -> RecoverableClient:
        with self._wlock:
            rc = self._workers.get(worker)
            if rc is None:
                rc = self._workers[worker] = self.svc.recoverable(
                    f"serve/{worker}", self._proc())
            return rc

    def recover(self, worker: str):
        """Crash-restart re-entry for a named worker thread.

        The replacement thread (same ``worker`` name, fresh coordination
        process) replays its predecessor's ledger and reclaims every slot
        lease that is still valid — fencing-checked, so a lease the table
        already re-granted comes back as lost, never double-held.  Returns
        the reclaimed leases; the worker resumes those batches (or
        ``complete``\\ s them) under the original fencing tokens.
        """
        client, reclaimed = self.svc.restart(f"serve/{worker}", self._proc())
        with self._wlock:
            self._workers[worker] = client
        return reclaimed

    def _admission_gate(self, key: str) -> None:
        """Brownout shedding: refuse an EXCLUSIVE admission fast when the
        overload layer already knows the slot's home is in trouble (open
        circuit breaker, or a retry budget too dry to fund even one retry
        round).  Read-lane admissions (:meth:`admit_read`) never come
        through here — shared-mode reads keep flowing while exclusive
        admits shed, which is the brownout contract.  A no-op when the
        service carries no :class:`~repro.coord.OverloadPolicy`."""
        ctl = self.svc.table.overload
        if ctl is None:
            return
        home = self.svc.home_of(key)
        if ctl.breaker_open(home):
            self.sheds += 1
            raise Overloaded(
                f"admission shed: breaker open for host {home}",
                reason="breaker", host=home)
        b = ctl.budget(home)
        if b.tokens < b.retry_cost:
            self.sheds += 1
            raise Overloaded(
                f"admission shed: retry budget dry for host {home}",
                reason="budget", host=home)

    def admit(self, timeout: Optional[float] = None,
              worker: Optional[str] = None,
              deadline: Optional[float] = None):
        """Take an EXCLUSIVE lease on any free write slot (round-robin scan,
        then block).

        The deadline and backoff run on the coordination service's injected
        clock/sleep pair, so an admission gate over a sim-backed (or
        fake-clock) table times out in that table's time base instead of
        wall time.  ``deadline`` is the absolute form (the earlier of the
        two wins); under overload control, admissions shed fast at the gate
        instead of scanning a slot list they cannot win (see
        :meth:`_admission_gate`).

        With a ``worker`` name the admission is ledgered (see
        :meth:`recover`); anonymous admissions take the bare path.
        """
        c = self._proc().counts
        local, rdma = c.local_and_rdma()
        opened, lease = obs.start("admission.admit"), None
        try:
            lease = self._admit(timeout, worker, deadline)
            return lease
        finally:
            local1, rdma1 = c.local_and_rdma()
            failed = lease is None
            obs.end(opened, key=None if failed else lease.key,
                    token=None if failed else lease.token,
                    local_ops=local1 - local, rdma_ops=rdma1 - rdma)

    def _admit(self, timeout, worker, deadline):
        clock, sleep = self.svc.table.clock, self.svc.table.sleep
        if timeout is not None:
            tdl = clock() + timeout
            deadline = tdl if deadline is None else min(deadline, tdl)
        rc = self._worker(worker) if worker is not None else None
        while True:
            for s in range(self.num_slots):
                key = f"serve/slot{s}"
                self._admission_gate(key)
                try:
                    if rc is not None:
                        lease = rc.try_acquire(key, self.ttl)
                    else:
                        lease = self.svc.try_acquire(self._proc(), key,
                                                     self.ttl)
                except Overloaded:
                    self.sheds += 1
                    raise
                if lease is not None:
                    return lease
            if deadline is not None and clock() > deadline:
                raise TimeoutError(f"no admission slot free in {timeout}s")
            sleep(0.002)  # back off: a full scan found no free slot

    def admit_read(self, timeout: Optional[float] = None):
        """Join a read lane with a SHARED lease (a single CAS; readers
        stack, so this only ever blocks while a quiesce drains the lanes).

        Requires ``read_slots > 0``.  The lane is chosen round-robin so
        concurrent readers spread their cohort CASes across lanes.
        Complete (and keepalive) a shared admission **on the thread that
        admitted it**: each server thread is its own coordination process,
        and the table's cohort-slot ledger is per process.  (Exclusive
        admissions are witness CASes and may be completed from any thread.)
        """
        if self.read_slots <= 0:
            raise ValueError("admit_read() needs read_slots > 0")
        clock, sleep = self.svc.table.clock, self.svc.table.sleep
        # Deliberately NOT gated by _admission_gate: the brownout contract
        # is that shared-mode reads keep flowing while exclusive admits
        # shed (a reader join is one CAS, zero RDMA on the serving host —
        # refusing it buys nothing).
        deadline = None if timeout is None else clock() + timeout
        p = self._proc()
        while True:
            for s in range(self.read_slots):
                lane = (p.pid + s) % self.read_slots
                lease = self.svc.try_acquire(
                    p, f"serve/readlane{lane}", self.ttl,
                    mode=LeaseMode.SHARED)
                if lease is not None:
                    return lease
            if deadline is not None and clock() > deadline:
                raise TimeoutError(f"no read lane joinable in {timeout}s")
            sleep(0.002)  # every lane is quiescing: wait out the drain

    def quiesce(self, lane: int = 0, timeout: Optional[float] = None):
        """Take an EXCLUSIVE lease on a read lane — the maintenance path.

        Arms the table's writer-intent barrier on the lane: no new readers
        join, the live cohort drains within one TTL, and the returned lease
        excludes every reader until it is released (``complete``).
        """
        if not (0 <= lane < self.read_slots):
            raise ValueError(f"lane {lane} out of range")
        clock, sleep = self.svc.table.clock, self.svc.table.sleep
        deadline = None if timeout is None else clock() + timeout
        while True:
            lease = self.svc.try_acquire(self._proc(), f"serve/readlane{lane}",
                                         self.ttl)
            if lease is not None:
                return lease
            if deadline is not None and clock() > deadline:
                raise TimeoutError(f"read lane {lane} not drained in {timeout}s")
            sleep(0.002)  # the drain barrier is armed; readers are leaving

    def keepalive(self, lease, worker: Optional[str] = None):
        """Renew mid-batch (call between prefill and decode, or per chunk).

        Rides the lock table's renewal fast path: one fencing-token-checked
        CAS on the expiry register, no shard ALock — and since the serving
        host is the table's local class, the keepalive costs **zero**
        simulated RDMA operations (``stats()['fast_renews']`` counts the
        fast-path hits; ``local_rdma_ops`` stays 0).
        """
        p = self._proc()
        c = p.counts
        local, rdma = c.local_and_rdma()
        opened = obs.start("admission.keepalive")
        try:
            if worker is not None:
                renewed = self._worker(worker).renew(lease)
            else:
                # Anonymous keepalives ride the per-thread async pipeline
                # (PR 10): home renewals resolve inline on the same
                # zero-RDMA fast path; remote ones ride the next flush as
                # one witness-CAS WR sharing a doorbell with queued work.
                pl = self._pipe()
                renewed = pl.sync(pl.renew(lease))
                self.svc.note_renewed(p, lease, renewed)
        finally:
            local1, rdma1 = c.local_and_rdma()
            obs.end(opened, key=lease.key, token=lease.token,
                    local_ops=local1 - local, rdma_ops=rdma1 - rdma)
        if renewed is None:
            raise RuntimeError(
                f"admission lease on {lease.key} lost (token {lease.token}); "
                "the batch overran its TTL and the slot was re-granted"
            )
        return renewed

    def complete(self, lease, worker: Optional[str] = None) -> bool:
        p = self._proc()
        c = p.counts
        local, rdma = c.local_and_rdma()
        opened = obs.start("admission.complete")
        try:
            if worker is not None:
                return self._worker(worker).release(lease)
            return self.svc.release(p, lease)
        finally:
            local1, rdma1 = c.local_and_rdma()
            obs.end(opened, key=lease.key, token=lease.token,
                    local_ops=local1 - local, rdma_ops=rdma1 - rdma)

    def stats(self) -> Dict:
        totals = self.svc.class_totals()
        rows = self.svc.telemetry()
        return {
            "slots": self.num_slots,
            "read_slots": self.read_slots,
            "grants": sum(r["grants"] for r in rows),
            "rejects": sum(r["rejects"] for r in rows),
            "grants_shared": sum(r["grants_shared"] for r in rows),
            "grants_exclusive": sum(r["grants_exclusive"] for r in rows),
            "shared_joins": sum(r["shared_joins"] for r in rows),
            "shared_releases": sum(r["shared_releases"] for r in rows),
            "intent_blocks": sum(r["intent_blocks"] for r in rows),
            "expirations": sum(r["expirations"] for r in rows),
            "fast_renews": sum(r["fast_renews"] for r in rows),
            "fast_releases": sum(r["fast_releases"] for r in rows),
            "reclaims": sum(r["reclaims"] for r in rows),
            "reclaim_fast": sum(r["reclaim_fast"] for r in rows),
            "reclaim_rejects": sum(r["reclaim_rejects"] for r in rows),
            "orphan_probes": sum(r["orphan_probes"] for r in rows),
            "orphan_adopts": sum(r["orphan_adopts"] for r in rows),
            "workers": len(self._workers),
            "local_rdma_ops": totals[0].rdma_ops,
            "local_ops": totals[0].local_ops,
            # Overload-protection telemetry (PR 9): admission-level sheds
            # plus the table-side shed/hedge/deadline counters; the
            # breaker/budget report appears only when a policy is armed.
            "sheds": self.sheds,
            "table_sheds": sum(r["sheds"] for r in rows),
            "hedges": sum(r["hedges"] for r in rows),
            "deadline_exceeded": sum(r["deadline_exceeded"] for r in rows),
            "overload": self.svc.overload_report(),
            # PR 10 pipeline telemetry, aggregated across server threads.
            "pipeline_flushes": sum(pl.stats["flushes"]
                                    for pl in self._pipes),
            "pipeline_flushed_ops": sum(pl.stats["flushed_ops"]
                                        for pl in self._pipes),
            "pipeline_hedge_rides": sum(pl.stats["hedge_rides"]
                                        for pl in self._pipes),
        }


@functools.partial(jax.jit, static_argnums=3)
def _next_token(logits, key, step, greedy: bool):
    """Sample ``[B, 1]`` tokens from the last position's logits, and say
    whether every logit of the step was finite."""
    with jax.named_scope("sample"):
        last = logits[:, -1]
        if greedy:
            tok = jnp.argmax(last, axis=-1)
        else:
            tok = jax.random.categorical(jax.random.fold_in(key, step), last)
        return tok[:, None].astype(jnp.int32), jnp.all(jnp.isfinite(logits))


def serve(
    arch: str,
    *,
    smoke: bool = True,
    batch: int = 4,
    prompt_len: int = 32,
    gen_len: int = 16,
    mesh_shape=(1, 1),
    mesh_axes=("data", "model"),
    greedy: bool = True,
    seed: int = 0,
    admission_slots: int = 0,
    admission_ttl: float = 30.0,
    admission: Optional[BatchAdmission] = None,
) -> Dict:
    cfg = get_config(arch, smoke=smoke)
    if not cfg.causal:
        raise ValueError(f"{arch} is encoder-only: no decode path")
    # A caller-supplied BatchAdmission is the real gate (shared across serve()
    # calls / server threads, bounding their concurrency); admission_slots
    # alone builds a private table, useful for the telemetry but never
    # contended by anyone else.
    if admission is None and admission_slots > 0:
        admission = BatchAdmission(num_slots=admission_slots, ttl=admission_ttl)
    mesh = make_mesh(mesh_shape, mesh_axes)
    model = Model(cfg)
    max_len = prompt_len + gen_len
    pshape = ShapeConfig("serve", seq_len=prompt_len, global_batch=batch,
                         kind="prefill")

    with jax.set_mesh(mesh):
        prefill_fn, _, (param_sh, batch_sh, _) = build_prefill_step(
            model, mesh, pshape, max_len
        )
        dshape = ShapeConfig("serve", seq_len=max_len, global_batch=batch,
                             kind="decode")
        decode_fn, _, _ = build_decode_step(model, mesh, dshape, max_len)

        # Parameters are created already sharded: no device ever holds an
        # unsharded copy.
        params = jax.jit(model.init, out_shardings=param_sh)(
            jax.random.PRNGKey(seed))
        prompts = input_specs(cfg, pshape, concrete=True,
                              rng=jax.random.PRNGKey(seed + 1))
        prompts = jax.device_put(prompts, batch_sh)
        sample_key = jax.random.PRNGKey(seed + 2)

        # Compile every program of the batch before admission: the slot TTL
        # must budget batch *execution*, not JIT compilation (a compile
        # outlasting the TTL would expire a healthy batch's lease and let
        # the slot be double-granted).  The loop calls these executables
        # directly, so an input that does not match them raises instead of
        # compiling again inside the lease and the timed region.
        with obs.span("serve.compile") as compiled:
            prefill_exe = prefill_fn.lower(params, prompts).compile()
            logits_info, cache_info = prefill_exe.out_info
            sample_first = _next_token.lower(
                logits_info, sample_key, 0, greedy).compile()
            decode_exe = decode_fn.lower(
                params, cache_info, sample_first.out_info[0]).compile()
            sample_next = _next_token.lower(
                decode_exe.out_info[0], sample_key, 0, greedy).compile()

        slot = admission.admit(timeout=admission_ttl) if admission else None
        try:
            with obs.span("serve.prefill") as prefilled:
                logits, caches = prefill_exe(params, prompts)
                jax.block_until_ready((logits, caches))
            with obs.span("serve.sample", step=0):
                tok, finite = sample_first(logits, sample_key, 0)
            finite_steps = [finite]
            if admission:
                slot = admission.keepalive(slot)  # prefill done; extend

            with obs.span("serve.pull", step=0):
                generated = [np.asarray(tok)]
            # Each step: decode, sample, and the pull of its token to the
            # host, which waits for the device.
            decoded = []
            for step in range(gen_len - 1):
                with obs.span("serve.decode", step=step + 1) as d:
                    logits, caches = decode_exe(params, caches, tok)
                decoded.append(d)
                with obs.span("serve.sample", step=step + 1):
                    tok, finite = sample_next(logits, sample_key, step + 1)
                finite_steps.append(finite)
                with obs.span("serve.pull", step=step + 1) as pulled:
                    generated.append(np.asarray(tok))
                if admission and step % 8 == 7:
                    slot = admission.keepalive(slot)  # TTL covers ~8 steps
        finally:
            # Release on *every* exit: an exception mid-batch must not hold
            # the slot hostage for the rest of its TTL.
            if admission:
                admission.complete(slot)

    tokens = np.concatenate(generated, axis=1)
    prefill_s = prefilled.seconds
    decode_s = ((pulled.end_ns - decoded[0].start_ns) * 1e-9
                if decoded else 0.0)
    out = {
        "tokens": tokens,
        "logits_finite": bool(np.all(jax.device_get(finite_steps))),
        "compile_seconds": compiled.seconds,
        "prefill_seconds": prefill_s,
        "decode_seconds_per_token": decode_s / max(gen_len - 1, 1),
        "throughput_tok_s": tokens.size / max(decode_s + prefill_s, 1e-9),
    }
    if admission:
        out["admission"] = dict(
            admission.stats(), slot_key=slot.key, fence_token=slot.token,
        )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--admission-slots", type=int, default=0,
                    help="admit the batch through the sharded lock table")
    args = ap.parse_args()
    use_compilation_cache()
    out = serve(args.arch, smoke=args.smoke, batch=args.batch,
                prompt_len=args.prompt_len, gen_len=args.gen, seed=args.seed,
                admission_slots=args.admission_slots)
    print(f"[serve] generated {out['tokens'].shape} tokens; "
          f"compile {out['compile_seconds']:.2f}s, "
          f"prefill {out['prefill_seconds']:.2f}s, "
          f"{out['decode_seconds_per_token'] * 1e3:.1f} ms/token, "
          f"{out['throughput_tok_s']:.1f} tok/s")
    print("[serve] first sequence:", out["tokens"][0][:16])
    if "admission" in out:
        print("[serve] admission:", out["admission"])


if __name__ == "__main__":
    main()
