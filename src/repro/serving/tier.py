"""Multi-process serving tier: worker processes + the wire protocol.

A tier is N independent engine instances, each a separate OS process
owning its own ``ServingEngine`` (own devices, own compiled functions —
the module-level compile caches make per-process spin-up cheap), fronted
by a ``serving.router.Router`` in the driver process.  No cross-process
collectives are involved: instances never communicate with each other,
only with the router, over ``multiprocessing.connection`` sockets
(length-prefixed pickles on localhost TCP with an authkey handshake).

Three worker roles share one loop (``worker_serve``):

  engine / decode   owns slots; autonomously steps whenever it has live
                    or queued work, answering RPCs between steps.  The
                    ``decode`` spelling is the disaggregated tier's
                    convention for an instance that only ever admits
                    pre-filled snapshots (``inject``) — the code path is
                    identical; what disaggregates is the traffic.
  prefill           owns NO slots: runs the engine's bucketed prefill on
                    submitted prompts and returns inject-ready snapshots
                    (``PrefillWorker``), so long prompts burn this
                    process's time, not a decode instance's tick loop.

State crosses processes as ``checkpoint.pack_tree`` buffers: one
request's DecodeState row (``engine.export_slot`` /
``PrefillWorker.prefill``) packs to a self-describing bytes blob the
receiver unpacks against its own config's structure
(``snapshot_like``) — the same raw-uint8 leaf container checkpoints
use, so bf16/int8 cache leaves round-trip exactly and drain/handoff
replay is byte-faithful.

Timing note: engines stamp Results with ``time.perf_counter``, whose
epoch is per-process — cross-process request metrics (latency, ttft)
are therefore the ROUTER's, measured on its own clock; per-engine
Result timestamps are only meaningful for requests that never moved.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from multiprocessing.connection import Client, Listener
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint, models
from repro.launch.host import forbid_jax_children
from repro.serving import engine as engine_mod
from repro.serving import sampling
from repro.serving.engine import Request

AUTHKEY = b"repro-serving-tier"


class TierError(RuntimeError):
    """A worker answered an RPC with an application error."""


# ------------------------------------------------------------------ wire ----

def request_to_wire(req: Request) -> dict:
    """Text-only requests cross the tier; frames/images stay
    single-process for now (the snapshot container carries only the
    token-path DecodeState)."""
    if req.frames is not None or req.image is not None \
            or req.image_embeds is not None:
        raise NotImplementedError(
            "the serving tier routes token requests only; encdec frames "
            "and vision inputs serve single-process (docs/serving.md)")
    return {"prompt": np.asarray(req.prompt, np.int64).tolist(),
            "max_new_tokens": int(req.max_new_tokens), "rid": int(req.rid)}


def request_from_wire(d: dict) -> Request:
    return Request(prompt=np.asarray(d["prompt"], np.int32),
                   max_new_tokens=int(d["max_new_tokens"]))


def result_to_wire(res) -> dict:
    return {"rid": res.rid, "prompt_len": res.prompt_len,
            "tokens": list(res.tokens), "t_submit": res.t_submit,
            "t_first": res.t_first, "t_done": res.t_done,
            "draft_proposed": res.draft_proposed,
            "draft_accepted": res.draft_accepted}


# -------------------------------------------------------------- snapshots ----

def snapshot_like(cfg, capacity: int, enc_len: int = 64):
    """Structure template for unpacking a one-row slot snapshot: the
    treedef/key-paths are what matters (shapes and dtypes come from the
    buffer's own manifest)."""
    cache = jax.eval_shape(
        lambda: models.init_decode_cache(cfg, 1, capacity, enc_len))
    return {"cache": cache, "pos": 0, "last_tok": 0, "slot_key": 0}


def pack_snapshot(snap: dict) -> bytes:
    return checkpoint.pack_tree(snap["arrays"], meta=snap["meta"])


def unpack_snapshot(buf: bytes, like) -> dict:
    arrays, meta = checkpoint.unpack_tree(buf, like)
    return {"arrays": arrays, "meta": meta}


# --------------------------------------------------------- prefill worker ----

class PrefillWorker:
    """Disaggregated prefill: the engine's bucketed length-masked prefill
    without any decode slots.  ``prefill`` turns one wire request into an
    inject-ready snapshot — a decode instance admits it through
    ``engine.import_snapshot`` and never runs a prefill itself, so long
    prompts stop head-of-line-blocking decode ticks.

    ``seed`` must match the decode instances' so the positional sampling
    key derived here (``slot_key(PRNGKey(seed), rid)``) continues the
    same stream the colocated engine would have sampled."""

    def __init__(self, params, cfg, *, capacity: int, buckets=None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 enc_len: int = 64):
        if cfg.family not in models.DECODE_FAMILIES or cfg.family == "encdec":
            raise NotImplementedError(
                f"prefill worker serves token families, got {cfg.family!r}")
        self.params, self.cfg, self.capacity = params, cfg, capacity
        bs = tuple(sorted(b for b in (buckets or engine_mod.DEFAULT_BUCKETS)
                          if b <= capacity))
        if not bs or bs[-1] < capacity:
            bs += (capacity,)
        self.buckets = bs
        self.temperature, self.top_k = temperature, top_k
        self.enc_len = enc_len
        self.rng = jax.random.PRNGKey(seed)
        self.prefills = 0

    def prefill(self, reqd: dict) -> dict:
        prompt = np.asarray(reqd["prompt"], np.int32)
        n = len(prompt)
        bucket = next((b for b in self.buckets if n <= b), None)
        if bucket is None:
            raise ValueError(f"prompt length {n} exceeds the largest "
                             f"bucket {self.buckets[-1]}")
        req_key = sampling.slot_key(self.rng, int(reqd.get("rid", 0)))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = prompt
        first, sub = engine_mod._prefill_fn(
            self.cfg, self.temperature, self.top_k, self.capacity, bucket)(
            self.params, jnp.asarray(toks), jnp.asarray([n], jnp.int32),
            {}, req_key)
        self.prefills += 1
        now = time.perf_counter()
        return {
            "arrays": {"cache": jax.device_get(sub.cache),
                       "pos": jax.device_get(sub.pos),
                       "last_tok": jax.device_get(first),
                       "slot_key": jax.device_get(req_key)},
            "meta": {"prompt": prompt.astype(np.int64).tolist(),
                     "max_new_tokens": int(reqd["max_new_tokens"]),
                     "prompt_len": n, "tokens": [int(first[0, 0])],
                     "t_submit": float(reqd.get("t_submit", now)),
                     "t_first": now, "rid": int(reqd.get("rid", -1)),
                     "draft_proposed": 0, "draft_accepted": 0},
        }


# ------------------------------------------------------------ worker loop ----

def worker_serve(obj, port: int, *, host: str = "127.0.0.1",
                 authkey: bytes = AUTHKEY, max_queue: Optional[int] = None):
    """Serve one ``ServingEngine`` or ``PrefillWorker`` to a single
    router connection until shutdown/disconnect.

    An engine worker steps AUTONOMOUSLY: whenever slots are live or the
    queue is non-empty it runs ``engine.step()`` and banks the finished
    results for the next ``poll``; RPCs are handled between steps.  This
    is what makes N instances genuinely concurrent — the router never
    drives ticks, it only feeds and drains them.

    Backpressure: a submit that finds no free slot and a full bounded
    queue (``max_queue``, default 2x slots) answers ``("defer", None)``
    instead of queueing unboundedly — the same defer-don't-fail
    semantics the block pool uses (serving/blocks.py); the router holds
    the request and retries on a later pump."""
    is_engine = isinstance(obj, engine_mod.ServingEngine)
    if is_engine and max_queue is None:
        max_queue = 2 * obj.slots
    with Listener((host, port), authkey=authkey) as listener:
        with listener.accept() as conn:
            if is_engine:
                _engine_loop(obj, conn, max_queue)
            else:
                _prefill_loop(obj, conn)


def _engine_loop(eng, conn, max_queue: int):
    done: List[dict] = []
    step_times: List[float] = []
    like = None
    while True:
        busy = any(r is not None for r in eng._active) or eng._queue
        if conn.poll(0.0 if busy else 0.02):
            try:
                cmd, payload = conn.recv()
            except EOFError:
                return                       # router went away: exit
            if cmd == "submit":
                if eng._draining:
                    conn.send(("draining", None))
                elif eng.free_slots == 0 and eng.queue_len >= max_queue:
                    conn.send(("defer", None))
                else:
                    rid = eng.submit(request_from_wire(payload))
                    conn.send(("ok", rid))
            elif cmd == "poll":
                conn.send(("ok", done))
                done = []
            elif cmd == "stats":
                st = eng.load()
                st["step_times"] = step_times
                st["decode_steps"] = eng.decode_steps
                step_times = []
                conn.send(("ok", st))
            elif cmd == "inject":
                if eng._draining:
                    conn.send(("draining", None))
                elif eng.free_slots == 0:
                    conn.send(("defer", None))
                else:
                    if like is None:
                        like = snapshot_like(eng.cfg, eng.capacity,
                                             eng.enc_len)
                    rid = eng.import_snapshot(unpack_snapshot(payload, like))
                    conn.send(("ok", rid))
            elif cmd == "drain":
                try:
                    snaps, queued = eng.drain()
                except NotImplementedError as e:
                    conn.send(("err", str(e)))
                    continue
                conn.send(("ok", ([pack_snapshot(s) for s in snaps],
                                  [request_to_wire(q) for q in queued])))
            elif cmd == "ping":
                conn.send(("ok", "pong"))
            elif cmd == "shutdown":
                conn.send(("ok", None))
                return
            else:
                conn.send(("err", f"unknown command {cmd!r}"))
        elif busy:
            t0 = time.perf_counter()
            finished = eng.step()
            step_times.append(time.perf_counter() - t0)
            done.extend(result_to_wire(r) for r in finished)


def _prefill_loop(pw, conn):
    while True:
        try:
            cmd, payload = conn.recv()
        except EOFError:
            return
        if cmd == "prefill":
            conn.send(("ok", pack_snapshot(pw.prefill(payload))))
        elif cmd == "stats":
            conn.send(("ok", {"prefills": pw.prefills, "free_slots": 0,
                              "queue_len": 0, "active": 0,
                              "draining": False, "step_times": []}))
        elif cmd == "ping":
            conn.send(("ok", "pong"))
        elif cmd == "shutdown":
            conn.send(("ok", None))
            return
        else:
            conn.send(("err", f"unknown command {cmd!r}"))


# --------------------------------------------------------------- handles ----

class InstanceHandle:
    """Router-side endpoint of one worker: a lazy socket + typed calls.
    Any transport failure (worker died, socket reset) surfaces as
    ``ConnectionError`` — the router's death-handling boundary."""

    def __init__(self, address, *, name: str = "", authkey: bytes = AUTHKEY,
                 proc: Optional[subprocess.Popen] = None):
        self.address = tuple(address)
        self.name = name or f"{self.address[0]}:{self.address[1]}"
        self.authkey, self.proc = authkey, proc
        self.dead = False
        self._conn = None

    def connect(self, timeout: float = 120.0):
        deadline = time.monotonic() + timeout
        while self._conn is None:
            try:
                self._conn = Client(self.address, authkey=self.authkey)
            except (ConnectionRefusedError, OSError):
                if self.proc is not None and self.proc.poll() is not None:
                    raise ConnectionError(
                        f"worker {self.name} exited with "
                        f"{self.proc.returncode} before accepting")
                if time.monotonic() > deadline:
                    raise ConnectionError(
                        f"worker {self.name} not accepting after "
                        f"{timeout:.0f}s")
                time.sleep(0.05)
        return self

    def call(self, cmd: str, payload=None):
        """-> (status, value).  status in {'ok', 'defer', 'draining'}."""
        if self.dead:
            raise ConnectionError(f"instance {self.name} is dead")
        if self._conn is None:
            self.connect()
        try:
            self._conn.send((cmd, payload))
            status, val = self._conn.recv()
        except (EOFError, OSError, BrokenPipeError) as e:
            raise ConnectionError(f"instance {self.name}: {e!r}") from e
        if status == "err":
            raise TierError(f"{self.name}: {val}")
        return status, val

    def shutdown(self, timeout: float = 10.0):
        try:
            if not self.dead:
                self.call("shutdown")
        except (ConnectionError, TierError):
            pass
        self.close(timeout=timeout)

    def close(self, timeout: float = 10.0):
        if self._conn is not None:
            self._conn.close()
            self._conn = None
        if self.proc is not None and self.proc.poll() is None:
            try:
                self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


# ---------------------------------------------------------------- spawning ----

def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def spawn_worker(role: str, model_args: List[str], *, port: Optional[int] = None,
                 env: Optional[dict] = None, name: str = "",
                 stdout=subprocess.DEVNULL) -> InstanceHandle:
    """Launch ``python -m repro.launch.serve --role <role> --port <p>
    <model_args>`` as a child process and hand back its (unconnected)
    handle.  ``model_args`` are plain serve.py flags — the same flags
    that describe a single-process engine describe each instance, which
    is what keeps a tier homogeneous (drain/handoff requires it)."""
    forbid_jax_children("spawn_worker")
    port = port or free_port()
    cmd = [sys.executable, "-m", "repro.launch.serve",
           "--role", role, "--port", str(port)] + list(model_args)
    env = {**os.environ, **(env or {})}
    src_dir = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))     # .../src
    env["PYTHONPATH"] = src_dir + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.Popen(cmd, env=env, stdout=stdout,
                            stderr=subprocess.STDOUT)
    return InstanceHandle(("127.0.0.1", port), proc=proc,
                          name=name or f"{role}:{port}")
