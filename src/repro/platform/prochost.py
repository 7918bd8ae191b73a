"""Cross-process PE hosting: one worker OS process per isolated node.

The paper's platform runs every PE in its own container; the seed repo ran
them all as threads of one process, so the fabric never paid a real
serialization or socket hop.  This module is the bridge between the two:
a node whose spec carries ``processIsolation: true`` gets a **worker
process** (spawned by the kubelet on first use), and every PE bound to
that node runs inside it.

Topology — control plane stays in the parent, data plane goes direct::

    parent process                       worker process (one per node)
    ┌──────────────────────────┐        ┌──────────────────────────┐
    │ operator / kubelet /     │  CTRL  │ WorkerHost               │
    │ HostBridge ──────────────┼────────┼── RpcChannel             │
    │   Fabric (registry,      │ frames │   WorkerFabric (proxy)   │
    │   epochs, partitions)    │        │   PERuntime threads      │
    │   SocketHub (parent PEs) │  DATA  │   SocketHub (rings)      │
    └────────────┬─────────────┘ frames └──────┬───────────────────┘
                 └────── tuple batches ────────┘   (worker ⇄ worker
                                                    flows never touch
                                                    the parent)

- **Control channel** (``RpcChannel``, F_CTRL frames): ``publish`` /
  ``unpublish`` / ``resolve`` / ``set_draining`` / ``partition`` and the
  RestFacade calls are forwarded to the parent, where the single
  authoritative ``Fabric`` registry (epochs, partition windows, residual
  carryover, drain gating) lives — so those semantics hold verbatim across
  the boundary.  Epoch movement is pushed back to workers as casts.
- **Data plane**: each worker runs a ``SocketHub``; its PEs' input rings
  register there, and ``publish`` forwards only the ``(address, token)``
  pair.  A sender in any process resolves to that pair and streams DATA
  frames directly — worker-to-worker traffic never relays through the
  parent.
- **Residual carryover**: a worker draining a PE ships the undelivered
  ring tail back over the control channel (``unpublish`` carries it); the
  parent stashes it like a local residual, and the next ``publish`` of the
  same name returns it for preload — whichever process that incarnation
  lands in.
- **Liveness**: a worker death closes its control channel; the bridge
  marks every endpoint it registered dead and bumps the fabric epoch, so
  ``endpoint_state`` classifies them ``retired`` (fail fast) instead of
  letting partition windows or retry envelopes spin on a process nothing
  can revive.  The pods restart through the normal failure chain and the
  kubelet respawns the worker on demand.

Worker nodes host *streams* PEs only: consistent regions and trainer
collectives need the checkpoint store and ICI group, which stay in-process
(such pods fail their start and stay pending on an isolated node).
"""

from __future__ import annotations

import itertools
import os
import socket
import subprocess
import sys
import threading
import time

from . import crds
from .transport import ShutDown, SocketHub, SocketSender, TupleQueue, \
    Unreachable
from .wire import DEFAULT_MAX_FRAME, F_CTRL, FrameDecoder, FrameError, \
    decode_value, encode_frame, encode_value

_ERR_TYPES = {"unreachable": Unreachable, "timeout": TimeoutError,
              "shutdown": ShutDown, "runtime": RuntimeError}

HANDSHAKE_TIMEOUT = 90.0  # worker import cost (jax) dominates first spawn


def _err_kind(e: Exception) -> str:
    if isinstance(e, Unreachable):
        return "unreachable"
    if isinstance(e, ShutDown):
        return "shutdown"
    if isinstance(e, TimeoutError):
        return "timeout"
    return "runtime"


class RpcChannel:
    """Bidirectional request/reply + cast messaging over one socket.

    Messages are codec dicts ``{id, kind: req|rep|cast, method, body}`` in
    F_CTRL frames.  A reader thread demultiplexes replies to waiting
    requesters and dispatches incoming requests/casts on fresh threads (a
    blocking handler — a 30 s ``resolve`` — must not stall the channel).
    Channel death wakes every waiter with ``Unreachable``.
    """

    def __init__(self, sock: socket.socket, dispatch, name: str = "rpc",
                 on_close=None, max_frame: int = DEFAULT_MAX_FRAME):
        self.sock = sock
        self.dispatch = dispatch  # (method, body, channel) -> reply value
        self.on_close = on_close
        self.max_frame = max_frame
        self.alive = True
        self._send_lock = threading.Lock()
        self._plock = threading.Lock()
        self._pending: dict = {}  # id -> [event, reply-body]
        self._seq = itertools.count(1)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._reader = threading.Thread(target=self._read_loop,
                                        name=f"{name}-reader", daemon=True)
        self._reader.start()

    def _send(self, msg: dict) -> None:
        frame = encode_frame(F_CTRL, encode_value(msg), self.max_frame)
        with self._send_lock:
            self.sock.sendall(frame)

    def request(self, method: str, body=None, timeout: float = 10.0):
        rid = next(self._seq)
        slot = [threading.Event(), None]
        with self._plock:
            self._pending[rid] = slot
        try:
            self._send({"id": rid, "kind": "req", "method": method,
                        "body": body})
        except (OSError, FrameError) as e:
            with self._plock:
                self._pending.pop(rid, None)
            raise Unreachable(f"control send {method}: {e}") from None
        if not slot[0].wait(timeout):
            with self._plock:
                self._pending.pop(rid, None)
            raise Unreachable(f"control rpc {method} timed out")
        rep = slot[1]
        if rep is None:  # channel died while we waited
            raise Unreachable(f"control channel closed during {method}")
        err = rep.get("err")
        if err is not None:
            kind, detail = err
            raise _ERR_TYPES.get(kind, RuntimeError)(detail)
        return rep.get("ok")

    def cast(self, method: str, body=None) -> None:
        try:
            self._send({"id": 0, "kind": "cast", "method": method,
                        "body": body})
        except (OSError, FrameError):
            pass  # fire-and-forget; channel death is handled by the reader

    def _read_loop(self) -> None:
        decoder = FrameDecoder(self.max_frame)
        try:
            while True:
                data = self.sock.recv(65536)
                if not data:
                    return
                for ftype, payload in decoder.feed(data):
                    if ftype == F_CTRL:
                        self._on_message(decode_value(payload))
        except (OSError, FrameError):
            return
        finally:
            self._finalize()

    def _on_message(self, msg: dict) -> None:
        kind = msg.get("kind")
        if kind == "rep":
            with self._plock:
                slot = self._pending.pop(msg["id"], None)
            if slot is not None:
                slot[1] = msg.get("body") or {}
                slot[0].set()
            return
        # req/cast: dispatch off-thread so a blocking handler (resolve)
        # cannot stall replies or other requests
        threading.Thread(target=self._handle, args=(msg,),
                         name="rpc-dispatch", daemon=True).start()

    def _handle(self, msg: dict) -> None:
        method, body, rid = msg.get("method"), msg.get("body"), msg.get("id")
        try:
            result = self.dispatch(method, body, self)
            rep = {"ok": result}
        except Exception as e:  # noqa: BLE001 — typed error travels back
            rep = {"err": [_err_kind(e), f"{type(e).__name__}: {e}"]}
        if msg.get("kind") == "req":
            try:
                self._send({"id": rid, "kind": "rep", "body": rep})
            except (OSError, FrameError):
                pass

    def _finalize(self) -> None:
        self.alive = False
        with self._plock:
            pending, self._pending = self._pending, {}
        for slot in pending.values():
            slot[0].set()  # reply stays None -> waiter raises Unreachable
        try:
            self.sock.close()
        except OSError:
            pass
        if self.on_close is not None:
            self.on_close()

    def close(self) -> None:
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass


class RemoteEndpoint:
    """Parent-registry handle for an input ring living in a worker.

    Stands where the ``TupleQueue`` would in ``Fabric._endpoints``: puts
    stream DATA frames to the owning worker's hub, ``closed``/``dead``
    drive the transport-liveness classification, and ``preload`` captures
    residual carryover for the bridge to ship to the worker (the real ring
    preloads there)."""

    def __init__(self, address, token: str, node: str):
        self.address = tuple(address)
        self.token = token
        self.node = node
        self.closed = False
        self.dead = False  # set when the owning worker process dies
        self.pending_residual: list | None = None
        self._sender = SocketSender(self.address, token)

    def put(self, item, timeout: float = 10.0) -> None:
        if self.closed or self.dead:
            raise ShutDown
        self._sender.put(item, timeout)

    def put_many(self, items, timeout: float = 10.0) -> None:
        if self.closed or self.dead:
            raise ShutDown
        self._sender.put_many(items, timeout)

    def preload(self, items) -> None:
        self.pending_residual = list(items)

    def take_all(self) -> list:
        # the worker drains the real ring and ships residuals over the
        # control channel (unpublish); a parent-side direct unpublish of a
        # live worker ring has nothing local to reclaim
        return []

    def close(self) -> None:
        self.closed = True
        self._sender.dispose()

    def __len__(self) -> int:
        return 0


class _WorkerClient:
    """Parent-side record of one worker process."""

    def __init__(self, node: str, channel: RpcChannel, data_addr):
        self.node = node
        self.channel = channel
        self.data_addr = tuple(data_addr)
        self.proc: subprocess.Popen | None = None
        self.pods: set = set()
        self.endpoints: list = []
        self.alive = True

    def start_pod(self, pod_name: str, job: str, pe_id: int, metadata: dict,
                  launch_count: int, standby: bool = False) -> None:
        self.channel.request("start_pod", {
            "pod": pod_name, "job": job, "pe": pe_id, "metadata": metadata,
            "launchCount": launch_count, "standby": standby}, timeout=15.0)
        self.pods.add(pod_name)

    def promote_pod(self, standby_name: str, primary_name: str,
                    launch_count: int) -> bool:
        """Promote a worker-hosted standby: the worker re-keys its pod map
        and wakes the runtime out of its hold under the primary name."""
        rep = self.channel.request("promote_pod", {
            "standby": standby_name, "primary": primary_name,
            "launchCount": launch_count}, timeout=10.0)
        if rep and rep.get("promoted"):
            self.pods.discard(standby_name)
            self.pods.add(primary_name)
            return True
        return False

    def stop_pod(self, pod_name: str, timeout: float = 5.0) -> None:
        self.pods.discard(pod_name)
        self.channel.request("stop_pod", {"pod": pod_name,
                                          "timeout": float(timeout)},
                             timeout=timeout + 5.0)

    def kill_pod(self, pod_name: str) -> bool:
        self.pods.discard(pod_name)
        rep = self.channel.request("kill_pod", {"pod": pod_name},
                                   timeout=10.0)
        return bool(rep and rep.get("killed"))

    def begin_drain(self, pod_name: str, request: dict) -> None:
        self.channel.request("begin_drain", {"pod": pod_name,
                                             "request": request},
                             timeout=10.0)

    def drain_upstream_gone(self, job: str, pe_id: int) -> None:
        self.channel.cast("drain_upstream_gone", {"job": job, "pe": pe_id})


class HostBridge:
    """Parent-side hub for worker processes (the kubelet owns one).

    Accepts worker control connections, answers their fabric/rest RPCs
    against the authoritative registry, pushes epoch movement, exposes
    parent-hosted rings to worker senders through its own data hub, and
    turns a worker death into retired endpoints + failed pods."""

    def __init__(self, fabric, rest, on_pod_exit, on_worker_lost,
                 max_frame: int = DEFAULT_MAX_FRAME):
        self.fabric = fabric
        self.rest = rest
        self.on_pod_exit = on_pod_exit      # (pod, crashed, drain_stats, stopped)
        self.on_worker_lost = on_worker_lost  # (node, [pod names])
        self.max_frame = max_frame
        self.hub = SocketHub(max_frame)  # parent-hosted rings, worker senders
        self._lock = threading.Lock()
        self._workers: dict = {}   # node -> _WorkerClient
        self._awaiting: dict = {}  # node -> threading.Event
        self._hub_tokens: dict = {}  # id(ring) -> token (parent rings exposed)
        self._stop = threading.Event()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(16)
        self.address = self._srv.getsockname()
        threading.Thread(target=self._accept_loop, name="bridge-accept",
                         daemon=True).start()
        threading.Thread(target=self._epoch_loop, name="bridge-epoch",
                         daemon=True).start()

    # ------------------------------------------------------ worker lifecycle

    def ensure_worker(self, node: str) -> _WorkerClient:
        """Return the node's live worker, spawning one if needed (first PE
        on an isolated node pays the process start; later PEs reuse it)."""
        with self._lock:
            client = self._workers.get(node)
            if client is not None and client.alive:
                return client
            event = self._awaiting.setdefault(node, threading.Event())
            event.clear()
        src_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = src_root + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env["REPRO_WORKER_NODE"] = node
        # a chip belongs to the process that opened it first; workers host
        # host-side PEs only and must never reach for the accelerator
        env["JAX_PLATFORMS"] = "cpu"
        env["REPRO_WORKER_PARENT"] = f"{self.address[0]}:{self.address[1]}"
        proc = subprocess.Popen(
            [sys.executable, "-c",
             "from repro.platform.prochost import worker_main; worker_main()"],
            env=env)
        if not event.wait(HANDSHAKE_TIMEOUT):
            proc.kill()
            raise RuntimeError(f"worker for {node} failed to handshake")
        with self._lock:
            client = self._workers[node]
            client.proc = proc
        return client

    def workers(self) -> dict:
        with self._lock:
            return dict(self._workers)

    def shutdown(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        for client in self.workers().values():
            try:
                client.channel.request("shutdown", timeout=5.0)
            except Exception:  # noqa: BLE001 — it may already be gone
                pass
            client.channel.close()
            if client.proc is not None:
                try:
                    client.proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    client.proc.kill()
                    client.proc.wait(timeout=5.0)
        self.hub.close()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            box: list = [None]  # filled by the hello dispatch
            channel = RpcChannel(
                conn,
                lambda method, body, ch, box=box:
                    self._dispatch(box, method, body, ch),
                name="bridge", on_close=lambda box=box:
                    self._worker_gone(box[0]),
                max_frame=self.max_frame)
            del channel  # owned by its reader thread / the client record

    def _worker_gone(self, client: _WorkerClient | None) -> None:
        if client is None or not client.alive:
            return
        client.alive = False
        # dead process: every endpoint it registered is unrevivable — the
        # epoch bump drops sender caches and the next classification sees
        # retired (fail fast), even inside a partition window
        for ep in client.endpoints:
            ep.dead = True
        self.fabric.invalidate()
        with self._lock:
            if self._workers.get(client.node) is client:
                del self._workers[client.node]
        try:
            self.rest.unregister_worker(client.node)
        except Exception:  # noqa: BLE001 — teardown races are benign
            pass
        pods = sorted(client.pods)
        client.pods.clear()
        if pods and not self._stop.is_set():
            self.on_worker_lost(client.node, pods)

    def _epoch_loop(self) -> None:
        last = self.fabric.epoch
        while not self._stop.is_set():
            cur = self.fabric.wait_epoch(last, timeout=0.5)
            if cur == last:
                continue
            last = cur
            for client in self.workers().values():
                client.channel.cast("epoch", {"epoch": cur})

    # ------------------------------------------------------- worker dispatch

    def _dispatch(self, box: list, method: str, body, channel: RpcChannel):
        if method == "hello":
            client = _WorkerClient(body["node"], channel, body["dataAddr"])
            box[0] = client
            with self._lock:
                self._workers[client.node] = client
                event = self._awaiting.get(client.node)
            self.rest.register_worker(client.node, {
                "dataAddr": list(client.data_addr)})
            if event is not None:
                event.set()
            return {"epoch": self.fabric.epoch}
        client = box[0]
        if client is None:
            raise RuntimeError("rpc before hello")
        if method == "publish":
            ep = RemoteEndpoint(client.data_addr, body["token"], client.node)
            self.fabric.publish(body["job"], body["pe"], body["port"], ep)
            client.endpoints.append(ep)
            residuals = ep.pending_residual or []
            ep.pending_residual = None
            return {"epoch": self.fabric.epoch, "residuals": residuals}
        if method == "unpublish":
            residuals = {int(k): v for k, v in
                         (body.get("residuals") or {}).items()}
            self.fabric.unpublish_pe(body["job"], body["pe"],
                                     residuals=residuals)
            return {"epoch": self.fabric.epoch}
        if method == "resolve":
            return self._resolve_for(client, body)
        if method == "set_draining":
            return {"marked": self.fabric.set_draining(body["job"],
                                                       body["pe"]),
                    "epoch": self.fabric.epoch}
        if method == "partition":
            self.fabric.partition(body["job"], body["pe"], body["duration"])
            return {"epoch": self.fabric.epoch}
        if method == "heal":
            return {"healed": self.fabric.heal(body["job"], body["pe"]),
                    "epoch": self.fabric.epoch}
        if method == "endpoint_state":
            return {"state": self.fabric.endpoint_state(body["job"],
                                                        body["pe"])}
        if method == "pe_published":
            return {"published": self.fabric.pe_published(body["job"],
                                                          body["pe"])}
        if method == "publish_count":
            return {"count": self.fabric.publish_count(body["job"],
                                                       body["pe"])}
        if method == "rest":
            name = body["method"]
            if name not in ("notify_connected", "notify_source_done",
                            "report_metrics", "report_sink",
                            "notify_checkpoint", "notify_standby_warm"):
                raise RuntimeError(f"rest method {name!r} not forwarded")
            getattr(self.rest, name)(*body.get("args", []))
            return None
        if method == "rest_req":
            if body["method"] != "get_cr_state":
                raise RuntimeError(f"rest_req {body['method']!r} not allowed")
            return self.rest.get_cr_state(*body.get("args", []))
        if method == "pod_exit":
            pod = body["pod"]
            client.pods.discard(pod)
            self.on_pod_exit(pod, body.get("crashed", False),
                             body.get("drainStats"),
                             body.get("stopped", False))
            return None
        raise RuntimeError(f"unknown bridge rpc {method!r}")

    def _resolve_for(self, client: _WorkerClient, body: dict) -> dict:
        q = self.fabric.resolve(body["job"], body["pe"], body["port"],
                                timeout=body.get("timeout", 30.0),
                                include_draining=body.get("includeDraining",
                                                          False))
        epoch = self.fabric.epoch
        if isinstance(q, RemoteEndpoint):
            if q.node == client.node:
                # co-located: the worker delivers straight into its own ring
                return {"kind": "local", "token": q.token, "epoch": epoch}
            return {"kind": "remote", "addr": list(q.address),
                    "token": q.token, "epoch": epoch}
        # parent-hosted ring: expose it through the bridge's data hub so the
        # worker can stream to it (token registration is idempotent)
        token = self.hub.register(q)
        return {"kind": "remote", "addr": list(self.hub.address),
                "token": token, "epoch": epoch}


# ============================================================== worker side


class WorkerFabric:
    """The fabric surface a PE runtime sees inside a worker process.

    Rings for this worker's own input ports are real local ``TupleQueue``s
    (registered with the worker's data hub); everything about *names* —
    publish, resolve, drain marks, partition windows, restart detection —
    is forwarded to the parent's authoritative registry over the control
    channel.  ``epoch`` is a locally-cached copy advanced by pushes and by
    every reply, so ``EndpointCache`` invalidation behaves exactly as
    in-process (at worst one push-latency behind, which the epoch contract
    already absorbs)."""

    def __init__(self, channel: RpcChannel, hub: SocketHub):
        self.channel = channel
        self.hub = hub
        self.epoch = 0
        self.dns_delay = 0.0  # applied by the parent's resolve
        self._elock = threading.Lock()
        self._local: dict = {}    # (job, pe, port) -> (ring, token)
        self._senders: dict = {}  # (addr, token) -> SocketSender

    def note_epoch(self, epoch) -> None:
        with self._elock:
            if epoch is not None and epoch > self.epoch:
                self.epoch = epoch

    def make_queue(self, maxsize: int = 1024) -> TupleQueue:
        return TupleQueue(maxsize)

    def publish(self, job: str, pe_id: int, port_id: int, q) -> None:
        token = self.hub.register(q)
        rep = self.channel.request("publish", {
            "job": job, "pe": pe_id, "port": port_id, "token": token},
            timeout=15.0)
        if rep.get("residuals"):
            q.preload(rep["residuals"])
        self._local[(job, pe_id, port_id)] = (q, token)
        self.note_epoch(rep.get("epoch"))

    def unpublish_pe(self, job: str, pe_id: int) -> None:
        residuals: dict = {}
        for key in [k for k in self._local if k[:2] == (job, pe_id)]:
            q, token = self._local.pop(key)
            items = q.take_all()
            q.close()
            self.hub.unregister(token)
            if items:
                residuals[key[2]] = items
        rep = self.channel.request("unpublish", {
            "job": job, "pe": pe_id, "residuals": residuals}, timeout=15.0)
        self.note_epoch(rep.get("epoch"))

    def resolve(self, job: str, pe_id: int, port_id: int,
                timeout: float = 30.0, include_draining: bool = False):
        rep = self.channel.request("resolve", {
            "job": job, "pe": pe_id, "port": port_id,
            "timeout": float(timeout), "includeDraining": include_draining},
            timeout=float(timeout) + 10.0)
        self.note_epoch(rep.get("epoch"))
        if rep["kind"] == "local":
            ring = self.hub.lookup(rep["token"])
            if ring is None:
                raise ShutDown("co-located endpoint already retired")
            return ring
        key = (tuple(rep["addr"]), rep["token"])
        sender = self._senders.get(key)
        if sender is None:
            sender = SocketSender(key[0], rep["token"])
            self._senders[key] = sender
        return sender

    def set_draining(self, job: str, pe_id: int) -> int:
        rep = self.channel.request("set_draining",
                                   {"job": job, "pe": pe_id}, timeout=10.0)
        self.note_epoch(rep.get("epoch"))
        return rep.get("marked", 0)

    def partition(self, job: str, pe_id: int, duration: float) -> None:
        rep = self.channel.request("partition", {
            "job": job, "pe": pe_id, "duration": float(duration)},
            timeout=10.0)
        self.note_epoch(rep.get("epoch"))

    def heal(self, job: str, pe_id: int) -> bool:
        rep = self.channel.request("heal", {"job": job, "pe": pe_id},
                                   timeout=10.0)
        self.note_epoch(rep.get("epoch"))
        return bool(rep.get("healed"))

    def endpoint_state(self, job: str, pe_id: int) -> str:
        return self.channel.request("endpoint_state",
                                    {"job": job, "pe": pe_id},
                                    timeout=10.0)["state"]

    def pe_published(self, job: str, pe_id: int) -> bool:
        return bool(self.channel.request("pe_published",
                                         {"job": job, "pe": pe_id},
                                         timeout=10.0)["published"])

    def publish_count(self, job: str, pe_id: int) -> int:
        return int(self.channel.request("publish_count",
                                        {"job": job, "pe": pe_id},
                                        timeout=10.0)["count"])

    def collective(self, job: str, region: str, width: int):
        raise RuntimeError("collectives are unavailable on "
                           "process-isolated nodes")

    def abort_collectives(self, job: str) -> None:
        pass


class WorkerRest:
    """RestFacade proxy: notifications cast to the parent (where the real
    facade throttles, stamps heartbeats — clock-straggle windows included —
    and runs the connect envelope), mirrored-throttled here so the control
    channel never carries per-loop-iteration chatter."""

    def __init__(self, channel: RpcChannel):
        self.channel = channel
        self.ckpt = None  # consistent regions are gated off isolated nodes
        self._last_metric: dict = {}

    def _cast(self, method: str, args: list) -> None:
        self.channel.cast("rest", {"method": method, "args": args})

    def notify_connected(self, job: str, pe_id: int) -> None:
        self._cast("notify_connected", [job, pe_id])

    def notify_source_done(self, job: str, pe_id: int) -> None:
        self._cast("notify_source_done", [job, pe_id])

    def notify_standby_warm(self, job: str, pe_id: int,
                            step: int = -1) -> None:
        self._cast("notify_standby_warm", [job, pe_id, step])

    def report_metrics(self, job: str, pe_id: int, metrics: dict) -> None:
        key = (job, pe_id)
        now = time.monotonic()
        if not metrics.get("final") and \
                now - self._last_metric.get(key, 0.0) < 0.2:
            return
        self._last_metric[key] = now
        self._cast("report_metrics", [job, pe_id, metrics])

    def report_sink(self, job: str, pe_id: int, seen: int,
                    maxseq: int) -> None:
        self._cast("report_sink", [job, pe_id, seen, maxseq])

    def notify_checkpoint(self, job: str, region: str, pe_id: int,
                          step: int) -> None:
        self._cast("notify_checkpoint", [job, region, pe_id, step])

    def get_cr_state(self, job: str, region: str):
        return self.channel.request("rest_req", {
            "method": "get_cr_state", "args": [job, region]}, timeout=10.0)

    def get_routes(self, job: str, op_name: str) -> list:
        return []  # pub/sub import/export stays on in-process nodes

    def routes_epoch(self) -> int:
        return 0


class WorkerHost:
    """Runs inside the worker process: hosts PE runtimes for one node."""

    def __init__(self, sock: socket.socket, node: str,
                 hub: SocketHub | None = None):
        self.node = node
        self.hub = hub if hub is not None else SocketHub()
        self._exit = threading.Event()
        self.channel = RpcChannel(sock, self._dispatch,
                                  name=f"worker-{node}",
                                  on_close=self._exit.set)
        self.fabric = WorkerFabric(self.channel, self.hub)
        self.rest = WorkerRest(self.channel)
        self._plock = threading.Lock()
        self._pods: dict = {}  # pod name -> (runtime, stop_event)

    def hello(self) -> None:
        rep = self.channel.request("hello", {
            "node": self.node, "dataAddr": list(self.hub.address)},
            timeout=15.0)
        self.fabric.note_epoch(rep.get("epoch"))

    def run(self) -> None:
        """Block until the parent orders shutdown or its channel dies (an
        orphaned worker must not outlive the platform)."""
        self.hello()
        self._exit.wait()
        self._stop_all(timeout=2.0)
        self.hub.close()

    # ------------------------------------------------------- parent dispatch

    def _dispatch(self, method: str, body, channel: RpcChannel):
        if method == "start_pod":
            return self._start_pod(body)
        if method == "stop_pod":
            return self._stop_pod(body["pod"], body.get("timeout", 5.0))
        if method == "kill_pod":
            return {"killed": self._stop_pod(body["pod"], 5.0)["existed"]}
        if method == "promote_pod":
            return self._promote_pod(body)
        if method == "begin_drain":
            with self._plock:
                entry = self._pods.get(body["pod"])
            if entry is not None:
                entry[0].begin_drain(body["request"])
            return {"live": entry is not None}
        if method == "drain_upstream_gone":
            with self._plock:
                entries = list(self._pods.values())
            for runtime, _ in entries:
                if runtime.job == body["job"] and runtime.draining:
                    runtime.drain_upstream_gone(body["pe"])
            return None
        if method == "epoch":
            self.fabric.note_epoch(body.get("epoch"))
            return None
        if method == "shutdown":
            # reply first (return value), then unblock run() to exit
            threading.Timer(0.05, self._exit.set).start()
            return None
        if method == "ping":
            return {"node": self.node, "pods": len(self._pods)}
        raise RuntimeError(f"unknown worker rpc {method!r}")

    def _start_pod(self, body: dict):
        from .runtime import PERuntime  # deferred: jax import is heavy
        meta = body["metadata"]
        if meta.get("consistentRegion") or any(
                op.get("kind") == "trainer"
                for op in meta.get("operators", [])):
            raise RuntimeError(
                "process-isolated nodes host streams PEs only (consistent "
                "regions / trainers need the in-process checkpoint+ICI path)")
        stop = threading.Event()
        standby = bool(body.get("standby"))
        runtime = PERuntime(
            job=body["job"], pe_id=body["pe"], metadata=meta,
            fabric=self.fabric, rest=self.rest,
            launch_count=body.get("launchCount", 0), stop_event=stop,
            on_exit=self._on_runtime_exit, standby=standby,
            pod_name=body["pod"] if standby else None)
        with self._plock:
            self._pods[body["pod"]] = (runtime, stop)
        runtime.start()
        return None

    def _stop_pod(self, pod_name: str, timeout: float) -> dict:
        with self._plock:
            entry = self._pods.pop(pod_name, None)
        if entry is None:
            return {"existed": False}
        runtime, stop = entry
        stop.set()
        runtime.join(timeout=timeout)
        return {"existed": True}

    def _promote_pod(self, body: dict) -> dict:
        """Re-key a holding standby under the primary pod name and wake it
        into the data plane (mirrors ``KubeletController.adopt_standby`` +
        ``signal_promote`` for the in-process path)."""
        with self._plock:
            entry = self._pods.pop(body["standby"], None)
            if entry is None or body["primary"] in self._pods:
                if entry is not None:  # primary already live: put it back
                    self._pods[body["standby"]] = entry
                return {"promoted": False}
            self._pods[body["primary"]] = entry
        runtime, _ = entry
        runtime.promote(body.get("launchCount", 0))
        return {"promoted": True}

    def _on_runtime_exit(self, runtime) -> None:
        pod_name = (runtime.pod_name_override
                    or crds.pod_name(runtime.job, runtime.pe_id))
        with self._plock:
            self._pods.pop(pod_name, None)
        self.channel.cast("pod_exit", {
            "pod": pod_name, "crashed": runtime.crashed,
            "drainStats": runtime.drain_stats,
            "stopped": runtime.stop_event.is_set()})

    def _stop_all(self, timeout: float = 2.0) -> None:
        with self._plock:
            entries = list(self._pods.items())
            self._pods.clear()
        for _, (runtime, stop) in entries:
            stop.set()
        for _, (runtime, _) in entries:
            runtime.join(timeout=timeout)


def worker_main() -> None:
    """Entry point of the spawned worker process (see
    ``HostBridge.ensure_worker``); parent address + node name arrive via
    environment so the command line stays a plain importable ``-c``."""
    parent = os.environ["REPRO_WORKER_PARENT"]
    node = os.environ["REPRO_WORKER_NODE"]
    host, _, port = parent.rpartition(":")
    sock = socket.create_connection((host, int(port)), timeout=10.0)
    WorkerHost(sock, node).run()
