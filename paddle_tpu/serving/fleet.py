"""Fleet serving: N engine replicas behind one prefix-affinity router.

One ``ContinuousBatchingScheduler`` on one host is a single-replica
story; this module composes the existing pieces into the
millions-of-users shape (ROADMAP item 3):

- **Replicas** — each replica is ONE OS process running a full serving
  stack (``ServingEngine`` + scheduler + SLO
  tracker + per-replica ``/metrics``/``/healthz``/``/status``),
  spawned via :func:`paddle_tpu.distributed.spawn`'s store-backed
  rendezvous and warm-started with ``from_checkpoint`` when a
  checkpoint is given. The replica publishes its RPC + HTTP ports back
  through the rendezvous store (child-chosen ephemeral ports — N
  replicas on one host can never collide), then serves until told to
  shut down.
- **Router** — :class:`FleetRouter` front-ends the fleet: requests are
  routed with **prefix affinity** (:class:`~.router.
  PrefixAffinityRouter` — consistent hash over the first
  page-granularity token block, so same-prefix traffic lands on the
  replica already holding those KV pages and PR 11's cache turns the
  prefill into a page-table copy), falling back to least-loaded by
  queue depth + free KV pages when the preferred replica is saturated.
- **Elasticity** — the supervision tick replaces crashed replicas
  (same restart accounting the elastic relaunch controller uses:
  ``relaunch`` runlog events + ``paddle_elastic_restarts_total``) and
  re-enqueues the dead replica's in-flight requests at the router —
  idempotent by GLOBAL request id, so a replica SIGKILL under load
  costs throughput for a few seconds and **zero failed requests**.
  :class:`~.router.SLOAutoscaler` drives elastic sizing off PR 10's
  SLO burn rates: sustained TTFT/queue-wait burn scales out, a
  sustained idle fleet drains one replica (stop routing to it, let
  in-flight work finish) and retires it — scale-in never drops a
  request either.
- **Live migration** — a running request's KV pages move between
  replicas mid-decode: the source checkpoints (token ids, sampling
  cursor, uncached KV suffix gathered from the page table), the
  control plane streams chunked + sha256-checksummed payloads with
  bounded timeouts and backoff, the destination reuses any radix-cache
  prefix it already holds and resumes decode token-exact. Three paths
  ride on it: drain-by-migrate scale-in (with a drain deadline so
  retirement never hangs), mid-stream shedding off wedged/SLO-burning
  stragglers, and SIGKILL failover that re-prefills only the suffix
  the surviving fleet's prefix caches don't cover.
- **Federation** — every replica logs into ONE shared run dir
  (rank = replica id, per-rank ``requests.rank<k>.jsonl`` streams), so
  ``merge_run_dir`` already folds the whole fleet into one
  ``run_summary.json``; :meth:`FleetRouter.federate` adds the
  fleet-level section (routing stats, requeued rids, scale events,
  restarts). :meth:`FleetRouter.serve_http` exposes the fleet
  ``/status`` (per-replica health + pool + burn rates + aggregates)
  and a federated ``/metrics`` (per-replica series relabeled with
  ``replica="<k>"``).

The RPC plane is newline-delimited JSON over stdlib TCP sockets (one
short-lived connection per call, no framing state, no new
dependencies); the rendezvous store is the only other wire.

Quickstart::

    from paddle_tpu.serving.fleet import FleetRouter
    fleet = FleetRouter(cfg, checkpoint="gpt.pdparams", n_replicas=2,
                        engine_kwargs=dict(page_size=16,
                                           decode_buckets=(1, 2, 4)))
    fleet.start()
    rids = [fleet.submit(ids, max_new_tokens=32) for ids in prompts]
    fleet.run()                     # tick until drained
    out = fleet.results[rids[0]]["tokens"]
    fleet.shutdown()                # reap + retire + federate
"""
from __future__ import annotations

import base64
import hashlib
import json
import os
import random
import signal
import socket
import threading
import time

import numpy as np

from ..observability import lockwitness

__all__ = ["FleetRouter", "ReplicaHandle", "FleetError"]

_RPC_TIMEOUT_S = 60.0
_MIGRATE_CHUNK_BYTES = 256 * 1024


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _debug(msg: str):
    """Replica-startup breadcrumbs to stderr (PADDLE_FLEET_DEBUG=1) —
    a replica that wedges before its rendezvous publish is otherwise
    invisible (its RPC plane does not exist yet)."""
    if os.environ.get("PADDLE_FLEET_DEBUG"):
        import sys
        print(f"[fleet pid={os.getpid()}] {msg}", file=sys.stderr,
              flush=True)


class FleetError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# RPC plane: newline-delimited JSON over stdlib TCP
# ---------------------------------------------------------------------------

def _rpc_request(addr: tuple, payload: dict,
                 timeout: float | None = None,
                 retries: int | None = None) -> dict:
    """One call: connect, send one JSON line, read one JSON line.

    Hardened: every call carries a deadline (``PADDLE_FLEET_RPC_TIMEOUT_S``,
    default 60s) and transient socket errors retry with exponential
    backoff + full jitter (``PADDLE_FLEET_RPC_RETRIES`` extra attempts,
    base ``PADDLE_FLEET_RPC_RETRY_BASE_S``), mirroring the TCPStore
    retry contract. Callers whose ops are NOT safe to replay (e.g. the
    router's poll, which drains done-records) pass ``retries=0``;
    replica-side handlers make submit/migrate idempotent by rid so the
    default retry budget cannot double-apply them.
    """
    from ..observability import instrument as obs
    if timeout is None:
        timeout = _env_float("PADDLE_FLEET_RPC_TIMEOUT_S", _RPC_TIMEOUT_S)
    if retries is None:
        retries = max(int(_env_float("PADDLE_FLEET_RPC_RETRIES", 2)), 0)
    base = _env_float("PADDLE_FLEET_RPC_RETRY_BASE_S", 0.05)
    attempt = 0
    while True:
        try:
            with socket.create_connection(addr, timeout=timeout) as s:
                s.sendall(json.dumps(payload).encode() + b"\n")
                with s.makefile("rb") as f:
                    line = f.readline()
            if not line:
                raise ConnectionError(f"empty RPC reply from {addr}")
            return json.loads(line.decode())
        except OSError:
            if attempt >= retries:
                raise
            attempt += 1
            obs.fleet_rpc_retries_counter().inc(
                op=str(payload.get("op") or "?"))
            time.sleep(base * (2 ** (attempt - 1)) * (1.0 + random.random()))


def _chunk_blob(blob: bytes) -> list:
    """Split a KV payload into wire chunks (PADDLE_FLEET_MIGRATE_CHUNK_BYTES,
    default 256 KiB)."""
    size = max(int(_env_float("PADDLE_FLEET_MIGRATE_CHUNK_BYTES",
                              _MIGRATE_CHUNK_BYTES)), 1)
    return [blob[i:i + size] for i in range(0, len(blob), size)]


class _RPCServer:
    """Replica-side accept loop (daemon threads, one per connection)."""

    def __init__(self, handler, host: str = "127.0.0.1"):
        self._handler = handler
        self._sock = socket.create_server((host, 0))
        self.host, self.port = self._sock.getsockname()[:2]
        self._closed = False
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True, name="fleet-rpc")
        self._thread.start()

    def _accept_loop(self):
        while not self._closed:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # closed
            threading.Thread(target=self._serve_one, args=(conn,),
                             daemon=True).start()

    def _serve_one(self, conn):
        try:
            conn.settimeout(_RPC_TIMEOUT_S)
            with conn, conn.makefile("rb") as f:
                line = f.readline()
                if not line:
                    return
                try:
                    reply = self._handler(json.loads(line.decode()))
                except Exception as e:  # a bad request must not kill serving
                    reply = {"ok": False, "error": repr(e)[:300]}
                conn.sendall(json.dumps(reply).encode() + b"\n")
        except Exception:
            pass

    def close(self):
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass


# ---------------------------------------------------------------------------
# replica process
# ---------------------------------------------------------------------------

def _build_engine(spec: dict):
    """Engine from a replica spec — warm start via ``from_checkpoint``
    when a checkpoint path is given, else a freshly built (seeded)
    model. Runs inside the replica process."""
    kind = spec.get("model_kind", "gpt")
    cfg = spec["config"]
    kw = dict(spec.get("engine_kwargs") or {})
    ckpt = spec.get("checkpoint")
    if kind == "gpt":
        from .engine import ServingEngine
        if ckpt:
            return ServingEngine.from_checkpoint(ckpt, cfg, **kw)
        import paddle_tpu as paddle
        from ..models.gpt import GPTForPretraining, GPTModel
        paddle.seed(int(spec.get("seed", 0)))
        return ServingEngine(GPTForPretraining(GPTModel(cfg)), cfg, **kw)
    raise FleetError(f"unknown model_kind {kind!r}")


def _fleet_replica_main(spec: dict):
    """Child entry (spawned by :meth:`FleetRouter._spawn_replica`):
    build the serving stack, publish RPC/HTTP endpoints through the
    rendezvous store, then run the scheduler loop until a ``shutdown``
    RPC arrives. An engine failure logs, re-raises, and kills the
    process — the router's supervision tick treats the dead process as
    a crash (re-enqueue + relaunch)."""
    # replica processes run on CPU by default: N engine processes on one
    # host cannot share the (exclusive-per-process) TPU; a multi-chip
    # deployment sets platform per replica instead
    platform = spec.get("platform", "cpu")
    os.environ["JAX_PLATFORMS"] = platform
    _debug(f"replica {spec.get('replica_id')} booting (platform "
           f"{platform})")
    import jax
    jax.config.update("jax_platforms", platform)
    # telemetry identity: rank = REPLICA id (spawn set rank-0 vars for
    # its 1-process pod), one shared fleet run dir, per-rank request
    # streams so N appenders never interleave
    rid = int(spec["replica_id"])
    os.environ["PADDLE_TRAINER_ID"] = str(rid)
    os.environ["PADDLE_REQUESTS_PER_RANK"] = "1"
    if spec.get("run_dir"):
        os.environ["PADDLE_TELEMETRY_DIR"] = spec["run_dir"]

    from ..observability.runlog import get_run_logger
    from ..observability.slo import SLOConfig
    from .scheduler import ContinuousBatchingScheduler

    _debug("building engine")
    engine = _build_engine(spec)
    _debug("engine built")
    slo = spec.get("slo")
    sched = ContinuousBatchingScheduler(
        engine, slo=SLOConfig(**slo) if isinstance(slo, dict) else slo,
        max_queue=int(spec.get("max_queue", 1024)),
        **dict(spec.get("scheduler_kwargs") or {}))
    http = sched.serve_http(port=0)  # ephemeral: replicas never collide
    stop = threading.Event()
    reported: set = set()
    submitted: set = set()      # rids ever admitted here (submit idempotency)
    mig_in: dict = {}           # rid -> staged inbound migration chunks
    mig_adopted: set = set()    # rids whose migrate_commit already applied
    # The control plane's turn. The loop below re-takes the scheduler's
    # lock the moment a step drops it, and Python's locks are not fair:
    # a poll, a submit or a migrate_out would wait out a whole burst of
    # decode steps (a request is over before `migrate` is answered, and
    # no poll ever sees it running). A call holds `turn` while it needs
    # the scheduler; the loop passes through `turn` between steps, so a
    # waiting call gets in at the next step boundary.
    turn = lockwitness.named_lock("fleet.replica_turn")

    def _migrate_out(msg: dict) -> dict:
        """Source side of a live migration: checkpoint the request,
        stream the uncached KV suffix to ``dest`` in checksummed
        chunks, and only release local state once the destination ACKs
        the commit. Any failure aborts: the checkpoint is restored to
        the run queue and the source stays authoritative."""
        gid = int(msg["rid"])
        dest = (msg["dest"][0], int(msg["dest"][1]))
        if not engine.can_migrate:
            return {"ok": True, "migrated": False,
                    "reason": "engine_unsupported"}
        with turn:
            ck = sched.checkpoint_request(gid)
        if ck is None:
            return {"ok": True, "migrated": False, "reason": "not_running"}
        t0 = time.monotonic()
        try:
            token_ids = list(ck["prompt"]) + list(ck["tokens"][:-1])
            begin = _rpc_request(dest, {
                "op": "migrate_begin", "rid": gid, "token_ids": token_ids,
                "prompt_len": len(ck["prompt"]),
                "max_new": int(ck["max_new"])})
            if not begin.get("accepted"):
                raise FleetError("destination refused migration: "
                                 f"{begin.get('reason') or begin.get('error')}")
            cached_len = int(begin.get("cached_len") or 0)
            k, v = engine.export_kv(gid, start=cached_len)
            blob = k.tobytes() + v.tobytes()
            chunks = _chunk_blob(blob)
            for i, ch in enumerate(chunks):
                rep = _rpc_request(dest, {
                    "op": "migrate_chunk", "rid": gid, "seq": i,
                    "data": base64.b64encode(ch).decode(),
                    "sha256": hashlib.sha256(ch).hexdigest()})
                if not rep.get("accepted"):
                    raise FleetError(
                        f"chunk {i} refused: {rep.get('reason')}")
            meta = {key: val for key, val in ck.items()}
            meta["migrate_bytes"] = (int(meta.get("migrate_bytes") or 0)
                                     + len(blob))
            commit = _rpc_request(dest, {
                "op": "migrate_commit", "rid": gid,
                "n_chunks": len(chunks),
                "sha256": hashlib.sha256(blob).hexdigest(),
                "kv_shape": [int(x) for x in k.shape],
                "kv_dtype": str(k.dtype), "meta": meta})
            if not commit.get("accepted"):
                raise FleetError("destination refused commit: "
                                 f"{commit.get('reason')}")
            with turn:
                sched.complete_migration(gid)
            engine.kv_migrations_out += 1
            engine.kv_migration_bytes += len(blob)
            return {"ok": True, "migrated": True, "bytes": len(blob),
                    "chunks": len(chunks), "cached_len": cached_len,
                    "payload_tokens": len(token_ids) - cached_len,
                    "migrate_s": round(time.monotonic() - t0, 6)}
        except Exception as e:
            # source stays authoritative: restore the checkpoint and
            # tell the destination to discard its half-applied staging
            with turn:
                sched.abort_migration(gid)
            try:
                _rpc_request(dest, {"op": "migrate_abort", "rid": gid},
                             timeout=2.0, retries=0)
            except Exception:
                pass
            return {"ok": True, "migrated": False, "reason": repr(e)[:200]}

    def handler(msg: dict) -> dict:
        op = msg.get("op")
        if op == "ping":
            return {"ok": True, "replica": rid}
        if op == "submit":
            gid = int(msg["rid"])
            if gid in submitted:
                # an RPC-retried submit whose first attempt landed:
                # accept idempotently, never double-admit a rid
                return {"ok": True, "accepted": True, "duplicate": True}
            r = sched.submit(np.asarray(msg["prompt"], np.int32),
                             int(msg["max_new"]), eos_id=msg.get("eos_id"),
                             rid=gid,
                             router_wait_s=float(msg.get("router_wait_s")
                                                 or 0.0),
                             deadline_s=msg.get("deadline_s"))
            if r.state == "rejected":
                # surfaced synchronously; keep reap from re-reporting
                # it; retry_after_s rides back so the router (and the
                # client behind it) gets the machine-readable backoff
                reported.add(r.rid)
                return {"ok": True, "accepted": False,
                        "reason": r.reject_reason,
                        "retry_after_s": r.retry_after_s}
            submitted.add(gid)
            return {"ok": True, "accepted": True}
        if op == "withdraw":
            # drain path: pull a queued/prefilling request back so the
            # router can re-dispatch it to a peer (running ones migrate)
            return {"ok": True,
                    "withdrawn": bool(sched.withdraw(int(msg["rid"])))}
        if op == "migrate_out":
            return _migrate_out(msg)
        if op == "migrate_begin":
            gid = int(msg["rid"])
            if gid in mig_in:  # idempotent by rid: restart staging
                mig_in.pop(gid, None)
                sched.abort_migration_in(gid)
            mig_adopted.discard(gid)
            ok2, res = sched.prepare_migration_in(
                gid, msg["token_ids"], int(msg["prompt_len"]),
                int(msg["max_new"]))
            if not ok2:
                return {"ok": True, "accepted": False, "reason": res}
            mig_in[gid] = {"chunks": {}, "t0": time.monotonic()}
            return {"ok": True, "accepted": True, "cached_len": int(res)}
        if op == "migrate_chunk":
            gid = int(msg["rid"])
            st = mig_in.get(gid)
            if st is None:
                return {"ok": True, "accepted": False, "reason": "no_begin"}
            data = base64.b64decode(msg["data"])
            if hashlib.sha256(data).hexdigest() != msg.get("sha256"):
                return {"ok": True, "accepted": False,
                        "reason": "chunk_checksum_mismatch"}
            st["chunks"][int(msg["seq"])] = data  # idempotent re-store
            return {"ok": True, "accepted": True}
        if op == "migrate_commit":
            gid = int(msg["rid"])
            st = mig_in.pop(gid, None)
            if st is None:
                if gid in mig_adopted:
                    # retried commit whose first attempt applied and
                    # whose ACK was lost: re-ACK, don't re-apply
                    return {"ok": True, "accepted": True,
                            "duplicate": True}
                return {"ok": True, "accepted": False, "reason": "no_begin"}
            n = int(msg["n_chunks"])
            if sorted(st["chunks"]) != list(range(n)):
                sched.abort_migration_in(gid)
                return {"ok": True, "accepted": False,
                        "reason": "missing_chunks"}
            blob = b"".join(st["chunks"][i] for i in range(n))
            if hashlib.sha256(blob).hexdigest() != msg.get("sha256"):
                sched.abort_migration_in(gid)
                return {"ok": True, "accepted": False,
                        "reason": "payload_checksum_mismatch"}
            shape = tuple(int(x) for x in msg["kv_shape"])
            dt = np.dtype(msg["kv_dtype"])
            half = int(np.prod(shape)) * dt.itemsize
            if len(blob) != 2 * half:
                sched.abort_migration_in(gid)
                return {"ok": True, "accepted": False,
                        "reason": "payload_size_mismatch"}
            k = np.frombuffer(blob[:half], dtype=dt).reshape(shape)
            v = np.frombuffer(blob[half:], dtype=dt).reshape(shape)
            meta = dict(msg.get("meta") or {})
            window = time.monotonic() - st["t0"]
            meta["migrate_s"] = float(meta.get("migrate_s") or 0.0) + window
            meta["migrate_window_s"] = window
            meta["rid"] = gid
            ok2, res = sched.adopt_migrated(meta, k, v)
            if not ok2:
                return {"ok": True, "accepted": False, "reason": res}
            mig_adopted.add(gid)
            submitted.add(gid)
            return {"ok": True, "accepted": True, "cached_len": int(res)}
        if op == "migrate_abort":
            gid = int(msg["rid"])
            if mig_in.pop(gid, None) is not None:
                sched.abort_migration_in(gid)
            return {"ok": True}
        if op == "poll":
            done = []
            with sched._lock:
                for r in (sched.finished + sched.rejected
                          + sched.deadline_exceeded):
                    if r.rid in reported:
                        continue
                    reported.add(r.rid)
                    done.append({"rid": r.rid, "state": r.state,
                                 "reject_reason": r.reject_reason,
                                 "retry_after_s": r.retry_after_s,
                                 "tokens": [int(t) for t in r.tokens],
                                 "summary": r.summary()})
            st = sched.status()
            st["replica"] = rid
            st["pid"] = os.getpid()
            st["http_url"] = http.url
            return {"ok": True, "done": done, "status": st}
        if op == "drain":
            sched.drain()
            return {"ok": True, "draining": True}
        if op == "shutdown":
            stop.set()
            return {"ok": True}
        return {"ok": False, "error": f"unknown op {op!r}"}

    def served(msg: dict) -> dict:
        # a migrate_out streams to its destination for as long as that
        # takes: it holds `turn` around its scheduler calls only
        if msg.get("op") == "migrate_out":
            return handler(msg)
        with turn:
            return handler(msg)

    rpc = _RPCServer(served)
    # publish endpoints through the spawn rendezvous store: the parent
    # blocks on these keys, so a replica that fails to build an engine
    # fails the startup handshake loudly instead of hanging the fleet
    from ..distributed.store import TCPStore
    host, port = os.environ["PADDLE_STORE_ENDPOINT"].rsplit(":", 1)
    store = TCPStore(host, int(port), is_master=False, world_size=1)
    try:
        store.set("fleet/rpc", f"{rpc.host}:{rpc.port}".encode())
        store.set("fleet/http", http.url.encode())
    finally:
        store.close()
    _debug(f"rendezvous published rpc={rpc.host}:{rpc.port}")

    logger = get_run_logger()
    if logger is not None:
        logger.log("replica_start", replica=rid, rpc_port=rpc.port,
                   http_url=http.url,
                   engine=type(engine).__name__,
                   warm_start=bool(spec.get("checkpoint")))
    last_flush = time.monotonic()
    try:
        while not stop.is_set():
            with turn:
                pass            # a waiting control-plane call goes first
            try:
                busy = sched.step() if sched.pending else False
            except Exception as e:
                if logger is not None:
                    logger.log("replica_engine_error", replica=rid,
                               error=repr(e)[:300])
                raise  # die nonzero -> supervisor relaunches
            if not busy:
                time.sleep(0.002)
            now = time.monotonic()
            if logger is not None and now - last_flush > 2.0:
                # periodic snapshot: a SIGKILLed replica still leaves
                # recent counters for the federated summary
                logger.flush_metrics()
                last_flush = now
    finally:
        if logger is not None:
            logger.log("replica_stop", replica=rid,
                       finished=len(sched.finished),
                       draining=sched.draining)
            logger.close()  # flushes metrics
        http.close()
        rpc.close()


# ---------------------------------------------------------------------------
# parent-side replica handle
# ---------------------------------------------------------------------------

class ReplicaHandle:
    """One spawned replica, parent side: process + RPC address + state."""

    def __init__(self, replica_id: int, spec: dict):
        from ..distributed.spawn import spawn
        self.replica_id = int(replica_id)
        self.spec = spec
        self.draining = False
        self.retired = False
        self.launched_ts = time.monotonic()
        self.last_status: dict = {}
        self.poll_failures = 0              # consecutive failed polls
        self.last_shed_ts = 0.0
        self.drain_deadline = float("inf")
        # circuit breaker: consecutive control-plane RPC failures
        # (submit timeouts AND poll misses) open it; the regular poll
        # doubles as the half-open probe — one success closes it
        self.rpc_failures = 0
        self.breaker_open = False
        self._ctx = spawn(_fleet_replica_main, args=(spec,), nprocs=1,
                          join=False,
                          job_id=f"fleet{os.getpid()}r{replica_id}")
        self.proc = self._ctx.processes[0]
        try:
            ep = self._ctx._store.get("fleet/rpc").decode()
            self.http_url = self._ctx._store.get("fleet/http").decode()
        except Exception as e:
            self.stop(grace=False)
            raise FleetError(
                f"replica {replica_id} failed startup rendezvous: "
                f"{e!r}") from e
        host, port = ep.rsplit(":", 1)
        self.rpc_addr = (host, int(port))

    @property
    def pid(self):
        return self.proc.pid

    def alive(self) -> bool:
        return self.proc.is_alive()

    def rpc(self, payload: dict, timeout: float | None = None,
            retries: int | None = None) -> dict:
        reply = _rpc_request(self.rpc_addr, payload, timeout=timeout,
                             retries=retries)
        if not reply.get("ok"):
            raise FleetError(
                f"replica {self.replica_id} RPC {payload.get('op')!r} "
                f"failed: {reply.get('error')}")
        return reply

    def stop(self, grace: bool = True, timeout: float = 15.0):
        """Graceful shutdown (RPC + join), escalating to terminate."""
        if grace and self.alive():
            try:
                self.rpc({"op": "shutdown"}, timeout=10.0)
            except Exception:
                pass
        self.proc.join(timeout)
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(5.0)
        self._ctx._close()
        self.retired = True


# ---------------------------------------------------------------------------
# the fleet router
# ---------------------------------------------------------------------------

class FleetRouter:
    """Front-end over N serving-engine replicas (see module docstring).

    ``config`` is the model config (``GPTConfig`` / ``ErnieMoeConfig``);
    ``checkpoint`` warm-starts every replica via ``from_checkpoint``;
    ``engine_kwargs`` pass through to the engine (``prefix_cache=True``
    by default — affinity routing exists to feed it). ``policy`` is the
    routing policy (``affinity`` / ``round_robin`` / ``least_loaded``)
    and ``autoscaler`` an optional :class:`~.router.SLOAutoscaler`.
    """

    def __init__(self, config, *, checkpoint=None, n_replicas: int = 2,
                 model_kind: str = "gpt", engine_kwargs: dict | None = None,
                 scheduler_kwargs: dict | None = None,
                 policy: str = "affinity", affinity_block: int | None = None,
                 slo: dict | None = None, autoscaler=None,
                 run_dir: str | None = None, replica_platform: str = "cpu",
                 max_restarts: int = 3, max_queue: int = 4096, seed: int = 0):
        from .router import PrefixAffinityRouter
        self.config = config
        self.checkpoint = checkpoint
        self.model_kind = model_kind
        self.engine_kwargs = dict(engine_kwargs or {})
        self.scheduler_kwargs = dict(scheduler_kwargs or {})
        if model_kind == "gpt":
            self.engine_kwargs.setdefault("prefix_cache", True)
        self.n_replicas = int(n_replicas)
        self.replica_platform = replica_platform
        self.max_restarts = int(max_restarts)
        self.max_queue = int(max_queue)
        self.seed = int(seed)
        self.slo = slo
        self.autoscaler = autoscaler
        self.page_size = int(self.engine_kwargs.get("page_size", 16))
        self.policy = PrefixAffinityRouter(
            block_tokens=int(affinity_block or self.page_size),
            policy=policy)
        if run_dir is None:
            import tempfile
            run_dir = tempfile.mkdtemp(prefix="fleet_run_")
        self.run_dir = run_dir
        self.replicas: dict[int, ReplicaHandle] = {}
        self.retired: list = []
        self.restarts = 0
        self._next_replica = 0
        self._next_rid = 0
        self._queue: list = []          # router-held request dicts
        self._inflight: dict = {}       # rid -> request dict (dispatched)
        self.results: dict = {}         # rid -> terminal record
        self.requeued_rids: list = []
        self.scale_events: list = []
        self.migrations: list = []      # recent migration event dicts
        self.migrated_rids: list = []
        self.migrations_completed = 0
        self.migrations_failed = 0
        self.migration_bytes = 0
        self.shed_events: list = []
        self.breaker_events: list = []  # recent open/close transitions
        self._lock = lockwitness.named_rlock("fleet.router")
        self._boot_threads: list = []   # in-flight async relaunches
        self._started = False
        self._logger = None
        self._http = None

    # ------------------------------------------------------------ lifecycle
    def start(self):
        """Spawn the initial replica set — in parallel threads, since
        each rendezvous blocks on the replica's engine build — and the
        router's own telemetry stream (rank -1, controller convention)."""
        from ..observability.runlog import RunLogger
        if self._started:
            return self
        os.makedirs(self.run_dir, exist_ok=True)
        self._logger = RunLogger(self.run_dir, rank=-1, generation=0)
        ids, errs, threads = [], [], []
        for _ in range(self.n_replicas):
            ids.append(self._next_replica)
            self._next_replica += 1

        def boot(rid):
            try:
                h = ReplicaHandle(rid, self._spec(rid))
                with self._lock:
                    self.replicas[rid] = h
            except Exception as e:
                errs.append(e)
        for rid in ids:
            t = threading.Thread(target=boot, args=(rid,), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
        if errs:
            self.shutdown(federate=False)
            raise errs[0]
        self._update_replica_gauges()
        self._started = True
        self._logger.log("fleet_start",
                         replicas=sorted(self.replicas),
                         policy=self.policy.policy,
                         checkpoint=bool(self.checkpoint))
        return self

    def _spec(self, replica_id: int) -> dict:
        return {
            "replica_id": replica_id,
            "model_kind": self.model_kind,
            "config": self.config,
            "checkpoint": self.checkpoint,
            "engine_kwargs": dict(self.engine_kwargs),
            "scheduler_kwargs": dict(self.scheduler_kwargs),
            "run_dir": self.run_dir,
            "slo": self.slo,
            "platform": self.replica_platform,
            "seed": self.seed,
        }

    def _spawn_replica(self) -> int:
        rid = self._next_replica
        self._next_replica += 1
        handle = ReplicaHandle(rid, self._spec(rid))
        self.replicas[rid] = handle
        self._update_replica_gauges()
        return rid

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -------------------------------------------------------------- intake
    def submit(self, prompt_ids, max_new_tokens: int, eos_id=None,
               deadline_s: float | None = None) -> int:
        """Queue one request with a fleet-global rid; dispatched to a
        replica on this call when one is routable, else held at the
        router (and counted in the router queue depth the autoscaler
        watches). ``deadline_s`` (relative to now) rides the wire to
        the replica — and is enforced at the router too, so a request
        stuck behind open breakers still terminates."""
        if not self._started:
            raise FleetError("FleetRouter.start() first")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            now = time.monotonic()
            rec = {"rid": rid, "prompt": prompt,
                   "max_new": int(max_new_tokens), "eos_id": eos_id,
                   "enqueued_ts": now, "submit_ts": now, "requeues": 0,
                   "deadline_s": float(deadline_s)
                   if deadline_s is not None and deadline_s > 0
                   else None}
            if len(self._queue) >= self.max_queue:
                self._terminal(rec, state="rejected",
                               reject_reason="router_queue_full",
                               retry_after_s=self._router_retry_after())
                return rid
            self._queue.append(rec)
        self._dispatch_queued()
        return rid

    def _router_retry_after(self) -> float:
        """Router-level backpressure hint: prefer the max of what the
        replicas themselves report (their estimate prices backlog
        against the drain rate); fall back to the cap."""
        cap = _env_float("PADDLE_FLEET_RETRY_AFTER_CAP_S", 30.0)
        est = 0.0
        for h in self.replicas.values():
            ov = (h.last_status or {}).get("overload") or {}
            est = max(est, float(ov.get("retry_after_s") or 0.0))
        return round(min(est or cap, cap), 3)

    @property
    def outstanding(self) -> int:
        with self._lock:
            return len(self._queue) + len(self._inflight)

    def warmup(self, max_new_tokens: int = 1, timeout: float = 120.0):
        """One tiny request DIRECTLY to every live replica, bypassing
        the routing policy (an affinity hash would send every warmup
        to the same replica and leave the rest cold). First-execution
        costs — the first invocation of the AOT programs, device
        paging — land here instead of inside the first user request's
        TTFT. Blocks until the warmups finish; returns their rids."""
        with self._lock:
            targets = [rid for rid, h in self.replicas.items()
                       if h.alive() and not h.retired and not h.draining]
        rids = []
        for t in targets:
            with self._lock:
                rid = self._next_rid
                self._next_rid += 1
                rec = {"rid": rid,
                       "prompt": np.arange(4, dtype=np.int32),
                       "max_new": int(max_new_tokens), "eos_id": None,
                       "enqueued_ts": time.monotonic(), "requeues": 0}
            # dispatch is a blocking RPC — never under the router lock
            # (PTCY002): a stalled replica would freeze submit/status
            # on every other thread for the RPC timeout
            if self._dispatch(rec, t) == "accepted":
                rids.append(rid)
        deadline = time.monotonic() + timeout
        while any(r not in self.results for r in rids):
            if time.monotonic() > deadline:
                raise FleetError("fleet warmup timed out")
            # full supervision, not just polling: a replica that dies
            # ON its warmup request still gets requeued + relaunched
            self.tick()
            time.sleep(0.005)
        return rids

    # ------------------------------------------------------------- routing
    @staticmethod
    def _straggler_polls() -> int:
        return max(int(_env_float("PADDLE_FLEET_STRAGGLER_POLLS", 3)), 1)

    # ------------------------------------------------------ circuit breaker
    @staticmethod
    def _breaker_fails() -> int:
        return max(int(_env_float("PADDLE_FLEET_BREAKER_FAILS", 3)), 1)

    def _breaker_failure(self, h, op: str = "?"):
        """One consecutive control-plane RPC failure against a replica
        (submit timeout or poll miss). Past PADDLE_FLEET_BREAKER_FAILS
        the breaker opens: routing skips the replica, but the regular
        supervision poll keeps probing it — that poll IS the half-open
        probe, and its first success closes the breaker."""
        from ..observability import instrument as obs
        h.rpc_failures += 1
        if h.breaker_open or h.rpc_failures < self._breaker_fails():
            return
        h.breaker_open = True
        obs.fleet_breaker_events_counter().inc(event="open")
        ev = {"event": "open", "replica": h.replica_id,
              "failures": h.rpc_failures, "op": op, "ts": time.time()}
        with self._lock:
            self.breaker_events.append(ev)
            del self.breaker_events[:-64]
        if self._logger is not None:
            self._logger.log("fleet_breaker", transition="open",
                             replica=h.replica_id,
                             failures=h.rpc_failures, op=op)

    def _breaker_success(self, h):
        from ..observability import instrument as obs
        h.rpc_failures = 0
        if not h.breaker_open:
            return
        h.breaker_open = False
        obs.fleet_breaker_events_counter().inc(event="close")
        ev = {"event": "close", "replica": h.replica_id,
              "ts": time.time()}
        with self._lock:
            self.breaker_events.append(ev)
            del self.breaker_events[:-64]
        if self._logger is not None:
            self._logger.log("fleet_breaker", transition="close",
                             replica=h.replica_id)

    def _snapshots(self) -> dict:
        """Routing view of the live, started replicas. A replica that
        missed ``PADDLE_FLEET_STRAGGLER_POLLS`` consecutive polls is
        reported unhealthy: routing skips it and the supervision tick
        sheds its load."""
        out = {}
        for rid, h in self.replicas.items():
            if h.retired or not h.alive():
                continue
            st = h.last_status or {}
            pool = st.get("kv_pool") or {}
            wedged = h.poll_failures >= self._straggler_polls()
            out[rid] = {
                "healthy": st.get("healthy", True) and not wedged
                and not h.breaker_open,
                "draining": h.draining or st.get("draining", False),
                "queue_depth": int(st.get("queue_depth") or 0),
                "pending": int(st.get("queue_depth") or 0)
                + int(st.get("prefilling") or 0)
                + int(st.get("running") or 0)
                + int(st.get("migrating_out") or 0)
                + int(st.get("migrating_in") or 0),
                "free_pages": int(pool.get("free_pages") or 0),
                "num_pages": int(pool.get("num_pages") or 0),
            }
        return out

    def _dispatch_queued(self):
        from ..observability import instrument as obs
        # _dispatch is a blocking RPC — hold the lock only to pick the
        # next routable request, drop it across the RPC (PTCY002: a
        # stalled replica must not freeze submit/status/tick for the
        # RPC timeout), re-take it to commit the outcome. `attempted`
        # gives each rid at most one attempt per call (the old one-pass
        # semantics), so a transiently-refused request can't spin here.
        attempted = set()
        snaps = None
        while True:
            with self._lock:
                if snaps is None:
                    snaps = self._snapshots()
                now = time.monotonic()
                still_queued = []
                pick = target = None
                pages = 0
                for rec in self._queue:
                    dl = rec.get("deadline_s")
                    if dl is not None and rec.get("submit_ts") is not None \
                            and now - rec["submit_ts"] > dl:
                        # expired while held at the router (saturated
                        # fleet, open breakers): terminal NOW — a
                        # deadline bounds the wait wherever the request
                        # is waiting
                        self._terminal(rec, state="deadline_exceeded")
                        continue
                    if pick is None and rec["rid"] not in attempted:
                        need = -(-(len(rec["prompt"]) + rec["max_new"])
                                 // self.page_size)
                        tgt = self.policy.route(rec["prompt"], snaps,
                                                pages_needed=need)
                        if tgt is not None:
                            pick, target, pages = rec, tgt, need
                            continue   # held out of the queue in flight
                    still_queued.append(rec)
                self._queue = still_queued
                if pick is None:
                    obs.fleet_router_queue_gauge().set(
                        float(len(self._queue)))
                    return
                attempted.add(pick["rid"])
            outcome = self._dispatch(pick, target)
            with self._lock:
                if outcome == "accepted":
                    obs.fleet_routed_counter().inc(
                        outcome=self.policy.last_outcome or "?")
                    # optimistic load update so one tick's burst doesn't
                    # all pile onto the same snapshot
                    if target in snaps:
                        snaps[target]["pending"] += 1
                        snaps[target]["queue_depth"] += 1
                        snaps[target]["free_pages"] = max(
                            snaps[target]["free_pages"] - pages, 0)
                elif outcome == "queued":
                    self._queue.append(pick)
                    snaps = None   # stale after a refusal: refresh
                # "rejected": terminal result recorded; neither routed
                # nor load-updated — the replica refused it

    def _submit_rpc(self, handle, rec: dict) -> dict:
        wait_s = time.monotonic() - rec["enqueued_ts"]
        return handle.rpc({
            "op": "submit", "rid": rec["rid"],
            "prompt": [int(t) for t in rec["prompt"]],
            "max_new": rec["max_new"], "eos_id": rec["eos_id"],
            "router_wait_s": round(wait_s, 6),
            "deadline_s": rec.get("deadline_s")})

    def _hedge_candidates(self, rec: dict, exclude: int) -> list:
        """Next-best affinity candidates for a hedged submit: the
        rendezvous order after the preferred replica, restricted to
        healthy, non-draining peers. The global rid dedup makes a
        double-submit (original landed but its ACK timed out) land as
        ``duplicate: True`` — hedging is idempotent by construction."""
        from .router import affinity_key, rendezvous_order
        snaps = self._snapshots()
        ids = [rid for rid, s in snaps.items()
               if rid != exclude and s.get("healthy", True)
               and not s.get("draining")]
        if not ids:
            return []
        key = affinity_key(rec["prompt"], self.policy.block_tokens)
        return rendezvous_order(key, ids)

    def _dispatch(self, rec: dict, target: int) -> str:
        """Send one request to one replica. Returns ``"accepted"``
        (in-flight there), ``"queued"`` (transient refusal / dead
        replica — keep it at the router), or ``"rejected"`` (permanent:
        a terminal rejected result was recorded — no replica in this
        fleet can ever serve it, or the fleet is pushing back with a
        ``retry_after_s`` hint the client must honor).

        A submit that times out feeds the replica's circuit breaker
        and HEDGES: the same rid is offered to the next-best affinity
        candidates (idempotent by the global rid dedup), so one wedged
        replica costs one timeout, not one lost dispatch round."""
        from ..observability import instrument as obs
        handle = self.replicas.get(target)
        if handle is None:
            return "queued"
        try:
            reply = self._submit_rpc(handle, rec)
            self._breaker_success(handle)
        except Exception:
            self._breaker_failure(handle, op="submit")
            reply = None
            for cand in self._hedge_candidates(rec, exclude=target):
                h2 = self.replicas.get(cand)
                if h2 is None:
                    continue
                obs.fleet_hedged_submits_counter().inc()
                if self._logger is not None:
                    self._logger.log("fleet_hedge", rid=rec["rid"],
                                     timed_out=target, hedged_to=cand)
                try:
                    reply = self._submit_rpc(h2, rec)
                    self._breaker_success(h2)
                    target = cand
                    break
                except Exception:
                    self._breaker_failure(h2, op="submit")
            if reply is None:
                return "queued"  # dead or wedged: _supervise decides
        if reply.get("accepted"):
            with self._lock:
                rec["replica"] = target
                self._inflight[rec["rid"]] = rec
            return "accepted"
        reason = str(reply.get("reason") or "?")
        if reason == "draining":
            return "queued"  # transient: another replica / next tick
        # retry_after / shed ARE terminal here: the routing policy
        # already picked the least-loaded viable replica, so its
        # backpressure speaks for the fleet — the hint reaches the
        # client instead of the request bouncing between full queues
        self._terminal(rec, state="rejected", reject_reason=reason,
                       retry_after_s=reply.get("retry_after_s"))
        return "rejected"

    def _terminal(self, rec: dict, state: str, reject_reason=None,
                  tokens=(), summary=None, retry_after_s=None):
        with self._lock:
            self.results[rec["rid"]] = {
                "rid": rec["rid"], "state": state,
                "reject_reason": reject_reason,
                "retry_after_s": retry_after_s,
                "tokens": list(tokens),
                "replica": rec.get("replica"),
                "requeues": rec.get("requeues", 0),
                "summary": summary,
            }
            self._inflight.pop(rec["rid"], None)

    # ---------------------------------------------------------- supervision
    def tick(self):
        """One supervision round: poll replicas (reap finished, refresh
        status), replace dead replicas (re-enqueue their in-flight
        requests), dispatch the router queue, complete drains, autoscale."""
        self._poll_replicas()
        self._supervise()
        self._dispatch_queued()
        self._finish_drains()
        self._autoscale()

    def _poll_replicas(self):
        # short deadline and NO retries: a wedged replica must not hang
        # the supervision tick, and a replayed poll could lose done-
        # records the replica already marked reported. Consecutive
        # failures accumulate; _snapshots/_supervise treat the replica
        # as a straggler past PADDLE_FLEET_STRAGGLER_POLLS of them.
        poll_timeout = _env_float("PADDLE_FLEET_POLL_TIMEOUT_S", 5.0)
        for rid, h in list(self.replicas.items()):
            if h.retired or not h.alive():
                continue
            try:
                reply = h.rpc({"op": "poll"}, timeout=poll_timeout,
                              retries=0)
            except Exception:
                h.poll_failures += 1
                self._breaker_failure(h, op="poll")
                continue  # _supervise decides dead-vs-slow by the process
            h.poll_failures = 0
            self._breaker_success(h)  # poll doubles as half-open probe
            h.last_status = reply.get("status") or {}
            with self._lock:
                for done in reply.get("done") or ():
                    gid = int(done["rid"])
                    if gid in self.results:
                        continue  # idempotent by request id
                    rec = self._inflight.pop(gid, None) or {"rid": gid}
                    rec.setdefault("replica", rid)
                    self._terminal(
                        rec, state=done["state"],
                        reject_reason=done.get("reject_reason"),
                        tokens=done.get("tokens") or (),
                        summary=done.get("summary"))

    def _requeue_one(self, rec: dict, from_replica, reason: str):
        """Pull one in-flight request back to the head of the router
        queue — the rid is the idempotency key, so a request the source
        already finished (and we already reaped) is never re-run."""
        from ..observability import instrument as obs
        with self._lock:
            self._inflight.pop(rec["rid"], None)
            rec["requeues"] += 1
            rec["enqueued_ts"] = time.monotonic()
            rec.pop("replica", None)
            self._queue.insert(0, rec)
            self.requeued_rids.append(rec["rid"])
            obs.fleet_requeued_counter().inc()
            if self._logger is not None:
                # visible in the fleet requests stream: the black-box
                # record that rid N survived a dead/wedged replica
                # (event != "request", so request folding never counts
                # it twice)
                self._logger.log_request({
                    "event": "request_requeue", "rid": rec["rid"],
                    "from_replica": from_replica, "reason": reason,
                    "requeues": rec["requeues"]})

    def _supervise(self):
        from ..observability import instrument as obs
        # mid-stream shedding: a live-but-wedged straggler (consecutive
        # poll misses) or an SLO-burning replica (opt-in via
        # PADDLE_FLEET_SHED_BURN) gets its in-flight load moved off NOW
        # rather than when it dies
        for rid, h in list(self.replicas.items()):
            if h.retired or not h.alive() or h.draining:
                continue
            if h.poll_failures >= self._straggler_polls():
                self.shed_replica(rid, reason="wedged")
            elif self._should_shed_burn(rid, h):
                self.shed_replica(rid, reason="slo_burn")
        for rid, h in list(self.replicas.items()):
            if h.retired or h.alive():
                continue
            # crashed (or SIGKILLed) replica: everything it held in
            # flight re-enqueues at the router
            del self.replicas[rid]
            self.retired.append(h)
            with self._lock:
                lost = [rec for rec in self._inflight.values()
                        if rec.get("replica") == rid]
            for rec in lost:
                self._requeue_one(rec, rid, reason="replica_dead")
            if h.draining:
                # a retiring replica died after drain: nothing to
                # relaunch — scale-in wanted it gone anyway
                self._update_replica_gauges()
                continue
            exitcode = h.proc.exitcode
            if self._logger is not None:
                self._logger.log("replica_dead", replica=rid,
                                 exitcode=exitcode,
                                 requeued=[rec["rid"] for rec in lost])
            if self.restarts >= self.max_restarts:
                self._update_replica_gauges()
                continue
            self.restarts += 1
            obs.restarts_counter().inc()
            # relaunch ASYNCHRONOUSLY: the replacement's engine build
            # takes seconds, and the surviving replicas must keep being
            # polled/dispatched meanwhile (the requeued requests go to
            # them right away — that IS the goodput recovery)
            with self._lock:
                new_rid = self._next_replica
                self._next_replica += 1

            def boot(new_rid=new_rid, dead=rid):
                try:
                    h = ReplicaHandle(new_rid, self._spec(new_rid))
                    with self._lock:
                        self.replicas[new_rid] = h
                    self._update_replica_gauges()
                except Exception as e:
                    if self._logger is not None:
                        self._logger.log("replica_relaunch_failed",
                                         replica=new_rid,
                                         error=repr(e)[:300])
            t = threading.Thread(target=boot, daemon=True,
                                 name=f"fleet-relaunch-{new_rid}")
            t.start()
            self._boot_threads.append(t)
            if self._logger is not None:
                # same event shape the elastic relaunch controller logs,
                # so merge_run_dir's restart tally needs zero new code
                self._logger.log("relaunch", restarts=self.restarts,
                                 dead_replica=rid, new_replica=new_rid)

    # ------------------------------------------------------ live migration
    def migrate(self, rid: int, target: int | None = None,
                timeout: float | None = None) -> dict:
        """Live-migrate one in-flight request to another replica: the
        source checkpoints it mid-decode, streams the KV-page payload
        (uncached suffix only) to ``target``, and releases its copy
        only after the destination ACKs — see ``_migrate_out`` for the
        replica-side protocol. Returns the source's reply dict with
        ``migrated`` True/False."""
        from ..observability import instrument as obs
        with self._lock:
            rec = self._inflight.get(int(rid))
            src = rec.get("replica") if rec else None
        if rec is None or src is None:
            return {"migrated": False, "reason": "not_inflight"}
        if target is None:
            pages = -(-(len(rec["prompt"]) + rec["max_new"])
                      // self.page_size)
            target = self.policy.migration_target(
                self._snapshots(), exclude=(src,), pages_needed=pages)
        if target is None or target == src:
            return {"migrated": False, "reason": "no_target"}
        src_h = self.replicas.get(src)
        dest_h = self.replicas.get(target)
        if src_h is None or dest_h is None or dest_h.rpc_addr is None:
            return {"migrated": False, "reason": "no_target"}
        if timeout is None:
            timeout = _env_float("PADDLE_FLEET_MIGRATE_TIMEOUT_S", 30.0)
        try:
            reply = src_h.rpc({"op": "migrate_out", "rid": int(rid),
                               "dest": list(dest_h.rpc_addr)},
                              timeout=timeout, retries=0)
        except Exception as e:
            reply = {"migrated": False, "reason": repr(e)[:200]}
        if not reply.get("migrated") and \
                reply.get("reason") == "not_running":
            # benign race: it finished (or is still queued) at the
            # source — neither a completed nor a failed migration
            return dict(reply, to=target)
        ev = {"rid": int(rid), "from": src, "to": target,
              "ok": bool(reply.get("migrated")),
              "reason": reply.get("reason"),
              "bytes": int(reply.get("bytes") or 0),
              "chunks": int(reply.get("chunks") or 0),
              "cached_len": int(reply.get("cached_len") or 0),
              "payload_tokens": int(reply.get("payload_tokens") or 0),
              "migrate_s": float(reply.get("migrate_s") or 0.0)}
        with self._lock:
            if ev["ok"]:
                rec["replica"] = target
                self.migrations_completed += 1
                self.migration_bytes += ev["bytes"]
                self.migrated_rids.append(int(rid))
                obs.fleet_migrations_counter().inc(outcome="completed")
                obs.fleet_migrated_bytes_counter().inc(float(ev["bytes"]))
            else:
                self.migrations_failed += 1
                obs.fleet_migrations_counter().inc(outcome="failed")
            self.migrations.append(dict(ev, ts=time.time()))
            del self.migrations[:-256]
        if self._logger is not None:
            # black-box record (event != "request": request folding
            # never double-counts it) that rid N moved replicas live
            self._logger.log_request(dict(ev, event="request_migrate"))
        return dict(reply, to=target)

    def _shed_burn_threshold(self) -> float:
        # opt-in: 0 disables SLO-burn shedding (wedged shedding is
        # always on); set PADDLE_FLEET_SHED_BURN=4.0 or similar
        return _env_float("PADDLE_FLEET_SHED_BURN", 0.0)

    def _should_shed_burn(self, rid: int, h) -> bool:
        thr = self._shed_burn_threshold()
        if thr <= 0:
            return False
        rates = ((h.last_status or {}).get("slo") or {})\
            .get("burn_rates") or {}
        burn = max((float(v) for v in rates.values()), default=0.0)
        if burn < thr:
            return False
        if time.monotonic() - h.last_shed_ts < \
                _env_float("PADDLE_FLEET_SHED_COOLDOWN_S", 5.0):
            return False
        snaps = self._snapshots()
        return any(r != rid and s.get("healthy", True)
                   and not s.get("draining") for r, s in snaps.items())

    def shed_replica(self, replica_id: int, reason: str = "manual") -> dict:
        """Move every in-flight request off a straggler / SLO-burning
        replica mid-stream: live-migrate each to a healthy peer,
        falling back to requeue-by-rid when the replica can't even
        answer RPC (wedged/SIGSTOPped — rid idempotency makes any
        eventual duplicate completion harmless)."""
        from ..observability import instrument as obs
        h = self.replicas.get(replica_id)
        out = {"replica": replica_id, "reason": reason,
               "migrated": 0, "requeued": 0}
        if h is None or h.retired:
            return out
        h.last_shed_ts = time.monotonic()
        with self._lock:
            recs = [rec for rec in self._inflight.values()
                    if rec.get("replica") == replica_id]
        wedged = h.poll_failures >= self._straggler_polls()
        for rec in recs:
            migrated = False
            if not wedged:  # don't burn a timeout per request on a
                migrated = bool(       # replica that won't answer
                    self.migrate(rec["rid"]).get("migrated"))
            if migrated:
                out["migrated"] += 1
            else:
                self._requeue_one(rec, replica_id, reason=f"shed_{reason}")
                obs.fleet_migrations_counter().inc(
                    outcome="requeue_fallback")
                out["requeued"] += 1
        if recs:
            self.shed_events.append(dict(out, ts=time.time()))
            del self.shed_events[:-64]
            if self._logger is not None:
                self._logger.log("fleet_shed", **out)
        return out

    def _migrate_off(self, replica_id: int) -> int:
        """Drain-by-migrate: move a draining replica's in-flight work
        to its peers — running requests live-migrate (KV pages and
        all); queued/prefilling ones are withdrawn and re-dispatched."""
        h = self.replicas.get(replica_id)
        if h is None or h.retired or not h.alive():
            return 0
        with self._lock:
            recs = [rec for rec in self._inflight.values()
                    if rec.get("replica") == replica_id]
        moved = 0
        for rec in recs:
            res = self.migrate(rec["rid"])
            if res.get("migrated"):
                moved += 1
                continue
            if res.get("reason") == "not_running":
                # maybe queued/prefilling at the source: withdraw it
                # and let the router re-dispatch to a peer; if it
                # actually finished, withdraw is a no-op and the next
                # poll reaps the result
                try:
                    rep = h.rpc({"op": "withdraw", "rid": rec["rid"]},
                                retries=0)
                except Exception:
                    continue
                if rep.get("withdrawn"):
                    self._requeue_one(rec, replica_id,
                                      reason="drain_withdraw")
                    moved += 1
        return moved

    def _finish_drains(self):
        """Retire draining replicas: every tick drain-by-migrate moves
        their in-flight work to peers (running requests live-migrate,
        queued ones withdraw + re-dispatch), and the drain deadline
        guarantees retirement can never hang — past it the remainder
        requeues by rid and the replica is stopped anyway."""
        for rid, h in list(self.replicas.items()):
            if not h.draining or h.retired or not h.alive():
                continue
            self._migrate_off(rid)
            st = h.last_status or {}
            pending = (int(st.get("queue_depth") or 0)
                       + int(st.get("prefilling") or 0)
                       + int(st.get("running") or 0)
                       + int(st.get("migrating_out") or 0)
                       + int(st.get("migrating_in") or 0))
            with self._lock:
                inflight_here = [rec for rec in self._inflight.values()
                                 if rec.get("replica") == rid]
            if pending == 0 and not inflight_here:
                try:
                    self._poll_replicas()  # final reap before shutdown
                except Exception:
                    pass
                h.stop()
                del self.replicas[rid]
                self.retired.append(h)
                if self._logger is not None:
                    self._logger.log("replica_retired", replica=rid)
                self._update_replica_gauges()
            elif time.monotonic() > h.drain_deadline:
                for rec in inflight_here:
                    self._requeue_one(rec, rid, reason="drain_deadline")
                if self._logger is not None:
                    self._logger.log(
                        "replica_drain_deadline", replica=rid,
                        requeued=[rec["rid"] for rec in inflight_here])
                h.stop(grace=False)
                del self.replicas[rid]
                self.retired.append(h)
                self._update_replica_gauges()

    # ----------------------------------------------------------- autoscale
    def _burn_rate(self) -> float:
        burn = 0.0
        with self._lock:
            handles = list(self.replicas.values())
        for h in handles:
            rates = ((h.last_status or {}).get("slo") or {})\
                .get("burn_rates") or {}
            for v in rates.values():
                burn = max(burn, float(v))
        return burn

    def _autoscale(self):
        if self.autoscaler is None:
            return
        active = [rid for rid, h in self.replicas.items()
                  if not h.draining and not h.retired]
        busy = bool(self._queue or self._inflight) or any(
            (h.last_status or {}).get("queue_depth")
            or (h.last_status or {}).get("running")
            for h in self.replicas.values())
        decision = self.autoscaler.observe(
            replicas=len(active), burn_rate=self._burn_rate(), busy=busy,
            router_queue_depth=len(self._queue))
        if decision["action"] == "scale_out":
            self.scale_out(reason=decision["reason"])
        elif decision["action"] == "scale_in":
            self.scale_in(reason=decision["reason"])

    def scale_out(self, reason: str = "manual"):
        from ..observability import instrument as obs
        rid = self._spawn_replica()
        obs.fleet_scale_events_counter().inc(action="scale_out")
        ev = {"action": "scale_out", "replica": rid, "reason": reason,
              "ts": time.time()}
        self.scale_events.append(ev)
        if self._logger is not None:
            self._logger.log("fleet_scale", **ev)
        return rid

    def scale_in(self, replica_id: int | None = None,
                 reason: str = "manual"):
        """Drain-then-retire one replica (the least loaded, unless
        named): stop routing to it now; :meth:`tick` live-migrates its
        in-flight work to peers (drain-by-migrate) and retires it once
        empty — nothing is dropped, and nothing waits to finish."""
        from ..observability import instrument as obs
        candidates = {rid: h for rid, h in self.replicas.items()
                      if not h.draining and not h.retired and h.alive()}
        if replica_id is not None:
            candidates = {replica_id: self.replicas[replica_id]} \
                if replica_id in candidates else {}
        if len(self.replicas) <= 1 or not candidates:
            return None
        rid = min(candidates, key=lambda r: (
            int((candidates[r].last_status or {}).get("running") or 0)
            + int((candidates[r].last_status or {}).get("queue_depth")
                  or 0)))
        h = self.replicas[rid]
        h.draining = True
        # drain-by-migrate (see _finish_drains) with a hard deadline:
        # retirement can never hang on a wedged drain
        h.drain_deadline = time.monotonic() + _env_float(
            "PADDLE_FLEET_DRAIN_DEADLINE_S", 120.0)
        try:
            h.rpc({"op": "drain"})
        except Exception:
            pass  # if it died, _supervise handles it
        obs.fleet_scale_events_counter().inc(action="scale_in")
        ev = {"action": "scale_in", "replica": rid, "reason": reason,
              "ts": time.time()}
        self.scale_events.append(ev)
        if self._logger is not None:
            self._logger.log("fleet_scale", **ev)
        self._update_replica_gauges()
        return rid

    def _update_replica_gauges(self):
        from ..observability import instrument as obs
        g = obs.fleet_replicas_gauge()
        with self._lock:
            live = [h for h in self.replicas.values()
                    if not h.retired and h.alive()]
        g.set(float(sum(1 for h in live if not h.draining)),
              state="active")
        g.set(float(sum(1 for h in live if h.draining)), state="draining")

    # ------------------------------------------------------------- driving
    def run(self, timeout: float | None = None,
            tick_interval: float = 0.01) -> bool:
        """Tick until every submitted request has a terminal result.
        Returns True when drained, False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while self.outstanding:
            if deadline is not None and time.monotonic() > deadline:
                return False
            self.tick()
            if self.outstanding:
                time.sleep(tick_interval)
        return True

    # ---------------------------------------------------- fault injection
    def pid_of(self, replica_id: int):
        """FaultInjector interface: the live pid behind a replica id."""
        h = self.replicas.get(replica_id)
        if h is None or h.retired or not h.alive():
            return None
        return h.pid

    def kill_replica(self, replica_id: int, sig=signal.SIGKILL):
        """Game-day helper: SIGKILL one replica in place (see
        ``fleet.elastic.fault_injection.kill_replica``)."""
        pid = self.pid_of(replica_id)
        if pid is None:
            raise FleetError(f"no live replica {replica_id}")
        os.kill(pid, sig)
        return pid

    # ----------------------------------------------------------- federation
    def fleet_status(self) -> dict:
        """The fleet ``/status`` body: per-replica health + pool + burn
        rates, plus fleet aggregates (total pages, federated prefix hit
        rate, router queue, routing + scale accounting)."""
        per_replica = {}
        agg = {"pages_in_use": 0, "free_pages": 0, "num_pages": 0,
               "tokens_reused": 0, "pages_shared": 0,
               "prefix_lookups": 0, "prefix_hits": 0}
        # snapshot under the lock: the HTTP status thread runs this
        # while a supervision tick may be del-ing replica entries
        with self._lock:
            replicas = list(self.replicas.items())
        for rid, h in replicas:
            st = dict(h.last_status or {})
            st["alive"] = h.alive()
            st["draining"] = h.draining or st.get("draining", False)
            per_replica[str(rid)] = st
            pool = st.get("kv_pool") or {}
            for k in ("pages_in_use", "free_pages", "num_pages",
                      "tokens_reused", "pages_shared",
                      "prefix_lookups", "prefix_hits"):
                agg[k] += int(pool.get(k) or 0)
        agg["prefix_hit_rate"] = round(
            agg["prefix_hits"] / agg["prefix_lookups"], 4) \
            if agg["prefix_lookups"] else 0.0
        healthy = bool(replicas) and all(
            h.alive() and (h.last_status or {}).get("healthy", True)
            for _, h in replicas if not h.draining)
        return {
            "healthy": healthy,
            "ts": time.time(),
            "replicas": per_replica,
            "n_replicas": len(replicas),
            "router_queue_depth": len(self._queue),
            "inflight": len(self._inflight),
            "results": len(self.results),
            "requeued": len(self.requeued_rids),
            "restarts": self.restarts,
            "routing": self.policy.stats(),
            "autoscaler": self.autoscaler.snapshot()
            if self.autoscaler is not None else None,
            "scale_events": self.scale_events[-8:],
            "migrations": {
                "completed": self.migrations_completed,
                "failed": self.migrations_failed,
                "bytes": self.migration_bytes,
                "recent": self.migrations[-8:],
                "shed_events": self.shed_events[-8:],
            },
            "pool_aggregate": agg,
            "burn_rate": round(self._burn_rate(), 4),
            # fleet-level overload view: per-replica brownout modes +
            # breaker state, total deadline cancellations, and the
            # backpressure hint a rejected client would get right now
            "overload": {
                "modes": {str(rid): ((h.last_status or {})
                                     .get("overload") or {})
                          .get("mode", "?") for rid, h in replicas},
                "deadline_exceeded": sum(
                    int((h.last_status or {}).get("deadline_exceeded")
                        or 0) for _, h in replicas),
                "retry_after_s": self._router_retry_after(),
                "breakers": {str(rid): {"open": h.breaker_open,
                                        "rpc_failures": h.rpc_failures}
                             for rid, h in replicas},
                "breaker_events": self.breaker_events[-8:],
            },
        }

    def _federated_metrics(self) -> str:
        """One exposition for the whole fleet: the router process's own
        registry verbatim, then every replica's series relabeled with
        ``replica="<k>"`` (comments dropped — HELP/TYPE live in the
        router's section)."""
        from ..observability.metrics import get_registry
        import urllib.request
        parts = [get_registry().to_prometheus()]
        with self._lock:
            replicas = sorted(self.replicas.items())
        for rid, h in replicas:
            if h.retired or not h.alive():
                continue
            try:
                with urllib.request.urlopen(h.http_url + "/metrics",
                                            timeout=5) as resp:
                    text = resp.read().decode()
            except Exception:
                continue
            out = []
            for line in text.splitlines():
                if not line or line.startswith("#"):
                    continue
                name, _, rest = line.partition(" ")
                if "{" in name:
                    base, _, labels = name.partition("{")
                    name = f'{base}{{replica="{rid}",{labels}'
                else:
                    name = f'{name}{{replica="{rid}"}}'
                out.append(f"{name} {rest}")
            parts.append("\n".join(out))
        return "\n".join(p for p in parts if p) + "\n"

    def serve_http(self, host: str = "127.0.0.1", port: int = 0):
        """Fleet-level /status + federated /metrics + /healthz."""
        from ..observability.httpd import ServingStatusServer
        self._http = ServingStatusServer(
            status_fn=self.fleet_status, host=host, port=port,
            metrics_fn=self._federated_metrics)
        return self._http

    def federate(self, write: bool = True) -> dict:
        """Fold the shared fleet run dir into one ``run_summary.json``
        (every replica's metrics/events/requests — ``merge_run_dir``
        does the heavy lifting) and add the fleet section: routing
        stats, requeued rids, restarts, scale events, terminal-result
        tallies."""
        from ..observability.runlog import merge_run_dir
        if self._logger is not None:
            try:
                self._logger.flush_metrics()
            except Exception:
                pass
        summary = merge_run_dir(self.run_dir, write=False)
        states: dict = {}
        for rec in self.results.values():
            states[rec["state"]] = states.get(rec["state"], 0) + 1
        summary["fleet"] = {
            "replicas_launched": self._next_replica,
            "replicas_live": len(self.replicas),
            "replicas_retired": len(self.retired),
            "restarts": self.restarts,
            "requeued_rids": sorted(set(self.requeued_rids)),
            "router": self.policy.stats(),
            "router_results": states,
            "scale_events": list(self.scale_events),
            "migrations": {
                "completed": self.migrations_completed,
                "failed": self.migrations_failed,
                "bytes": self.migration_bytes,
                "migrated_rids": sorted(set(self.migrated_rids)),
            },
            "shed_events": list(self.shed_events),
            "breaker_events": list(self.breaker_events),
            "autoscaler": self.autoscaler.snapshot()
            if self.autoscaler is not None else None,
        }
        if write:
            out = os.path.join(self.run_dir, "run_summary.json")
            tmp = f"{out}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(summary, f, indent=2, sort_keys=True,
                          default=str)
            os.replace(tmp, out)
        return summary

    def shutdown(self, federate: bool = True):
        """Final reap, stop every replica, close the fleet endpoint,
        and (by default) write the federated run summary."""
        for t in self._boot_threads:
            # an async relaunch still building must land (or fail)
            # before we stop "every" replica — otherwise its process
            # would outlive the fleet
            t.join(timeout=_RPC_TIMEOUT_S)
        self._boot_threads = []
        try:
            self._poll_replicas()
        except Exception:
            pass
        for rid, h in list(self.replicas.items()):
            try:
                h.stop()
            except Exception:
                pass
            self.retired.append(h)
            del self.replicas[rid]
        if self._http is not None:
            self._http.close()
            self._http = None
        summary = None
        if federate and self._started:
            try:
                summary = self.federate()
            except Exception:
                pass
        if self._logger is not None:
            self._logger.log("fleet_stop", results=len(self.results),
                             restarts=self.restarts)
            self._logger.close()
            self._logger = None
        self._started = False
        return summary
