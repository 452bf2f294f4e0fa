"""True MPMD pipeline parallelism: one program per stage, DCN activation
exchange (ROADMAP item 3; PAPERS.md "Scaling Deep Learning Training with
MPMD Pipeline Parallelism").

The shipped interleaved-1F1B schedule (spmd/pipeline.py) is ONE SPMD
program: every device traces, compiles, and ticks the whole timetable in
lockstep, activations hop over ICI ppermutes. This module is the MPMD
formulation the pipeline docstring calls "a later optimization": each
stage is its OWN gang with its own jit program compiling only its
contiguous chunk of the layer stack, and activations/cotangents cross
stage boundaries as framed wire tensors over TCP (the DCN analogue).

What makes it correct WITHOUT global lockstep:

  * The tick order comes from the SAME instruction tables
    `interleaved_schedule` emits (and test_pipeline_schedule.py proves).
    Stage d executes row d of the tables cycle by cycle.
  * The scheduler emits each arrival-store directive (fstore/bstore) on
    the SAME cycle as the producer's send, and every consuming read
    happens on a strictly later cycle. TCP preserves per-channel order,
    so "store the frame arriving at cycle c into slot s" becomes "pop
    the NEXT frame off the channel and put it in slot s" — processing
    store directives in cycle order reconstructs the exact slot mapping
    the SPMD program maintains by construction. Data dependencies
    (a blocking recv) are the only cross-stage coupling.
  * Dtype discipline mirrors the SPMD cycle body bit for bit:
    activations travel in the compute dtype, cotangents travel fp32 and
    are cast to the chunk-output dtype at the pullback, parameter
    gradients and the loss accumulate fp32, everything is divided by M
    once at the end — so a 2-stage MPMD run matches the single-gang
    interleaved run to float tolerance (pinned by tests).

Wire format (modeled on serving's TPFKV1 KV-handoff frames): a
self-describing binary frame MAGIC | u32 header len | JSON header
(dtype/shape + transfer metadata) | raw bytes. Raw buffers rather than
npz because activations are usually bfloat16 (ml_dtypes), which numpy's
save path does not round-trip reliably.

Transport: `StageTransport` runs a background sender thread (serialize +
wire latency off the critical path) and a background receiver thread
(prefetch into a bounded queue) per ring, so the send/recv of microbatch
k+1 overlaps the compute of microbatch k. `double_buffer=False` degrades
to the synchronous send-then-compute form, whose losses and gradients
the double-buffered one must equal bit for bit. Every recv carries a BOUNDED deadline
(TPUFLOW_MPMD_RECV_TIMEOUT_S), and sends get their own generous deadline
(TPUFLOW_MPMD_SEND_TIMEOUT_S, default = the recv deadline — backpressure
from a peer mid-compile is normal and must NOT look like death): a peer
stage dying mid-transfer surfaces as MPMDTransferError/Timeout on the
survivors, which fails the rank promptly so the elastic supervisor can
relaunch the gang instead of the fleet wedging on an infinite block.

Env contract (plumbed by the @parallel gang launch alongside
MF_PARALLEL_*): MF_MPMD_PEERS is a comma-separated host:port list, one
entry per stage, indexed by MF_PARALLEL_NODE_INDEX.
"""

import json
import os
import queue
import socket
import struct
import threading
import time

import ml_dtypes  # noqa: F401  (registers bfloat16 & friends with numpy)
import numpy as np

from .. import knobs
from . import sanitizer
from .pipeline import interleaved_schedule

MAGIC = b"TPFMP1\n"
_HELLO = b"TPFMPH1\n"

# the two rings of the 1F1B schedule: activations ride +1, cotangents -1
CHAN_ACT = "act"
CHAN_COT = "cot"


class MPMDTransferError(RuntimeError):
    """A stage-to-stage transfer failed (peer died / frame corrupt)."""


class MPMDTransferTimeout(MPMDTransferError):
    """A bounded-deadline recv expired: the peer stage is presumed hung
    or dead. Raising (rather than blocking forever) is what lets the
    elastic supervisor reap and relaunch the gang."""




def encode_frame(meta, arr):
    """Frame one wire tensor: `meta` is JSON-safe transfer metadata
    (chan/m/v/cycle), `arr` any host or device array. Dtype-preserving:
    the raw buffer rides verbatim, bfloat16 included."""
    a = np.ascontiguousarray(np.asarray(arr))
    header = dict(meta)
    header["dtype"] = str(a.dtype)
    header["shape"] = list(a.shape)
    hb = json.dumps(header).encode("utf-8")
    return b"".join([MAGIC, struct.pack("<I", len(hb)), hb, a.tobytes()])


def decode_frame(data):
    """Inverse of encode_frame: returns (meta, array)."""
    if not data.startswith(MAGIC):
        raise MPMDTransferError("not an MPMD wire frame")
    off = len(MAGIC)
    (hlen,) = struct.unpack_from("<I", data, off)
    off += 4
    header = json.loads(data[off:off + hlen].decode("utf-8"))
    off += hlen
    dtype = np.dtype(header.pop("dtype"))
    shape = tuple(header.pop("shape"))
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    if len(data) != off + n * dtype.itemsize:
        raise MPMDTransferError("MPMD wire frame truncated")
    arr = np.frombuffer(data, dtype, count=n, offset=off).reshape(shape)
    return header, arr


# ---------------------------------------------------------------------------
# Stage plan: validation + the shared schedule tables
# ---------------------------------------------------------------------------


class MPMDPlan(object):
    """One pipeline's static plan: the interleaved-1F1B instruction
    tables (shared verbatim with the SPMD path) plus the chunk→layer
    mapping each stage slices its parameters with."""

    def __init__(self, num_microbatches, num_virtual_stages, num_stages,
                 n_layers):
        M, V, S, L = (int(num_microbatches), int(num_virtual_stages),
                      int(num_stages), int(n_layers))
        if M < 1:
            raise ValueError("num_microbatches must be >= 1")
        if V < 1:
            raise ValueError("num_virtual_stages must be >= 1")
        if S < 2:
            raise ValueError(
                "MPMD needs num_stages >= 2 (one gang per stage); a "
                "single stage is the plain microbatched loss — use "
                "pipeline_train_interleaved/_degenerate_train")
        if L % (V * S):
            raise ValueError(
                "n_layers=%d must divide into num_virtual_stages*"
                "num_stages=%d chunks" % (L, V * S))
        self.M, self.V, self.S, self.n_layers = M, V, S, L
        self.Lc = L // (V * S)
        self.tables = interleaved_schedule(M, V, S)
        self.n_cycles = self.tables["n_cycles"]

    def layers_for_stage(self, stage):
        """Natural layer indices owned by `stage`, in the executor's
        local order (chunk-major: chunks stage, stage+S, ...)."""
        d, S, V, Lc = int(stage), self.S, self.V, self.Lc
        return [(j * S + d) * Lc + k for j in range(V) for k in range(Lc)]

    def describe(self):
        return {"num_microbatches": self.M, "num_virtual_stages": self.V,
                "num_stages": self.S, "n_layers": self.n_layers,
                "n_cycles": int(self.n_cycles)}


def plan_stages(num_microbatches, num_virtual_stages, num_stages, n_layers):
    """Build (and validate) the MPMD stage plan. The static analyzer's
    flow-level pass (`analysis/spmd_check.py`) checks literal calls to
    this against the flow's gang size and TPU topology BEFORE launch."""
    return MPMDPlan(num_microbatches, num_virtual_stages, num_stages,
                    n_layers)


def slice_stage_params(plan, stage, layer_stack):
    """Slice a natural-order stacked-layer pytree down to `stage`'s
    chunks, in the executor's local (chunk-major) order."""
    import jax

    idx = np.asarray(plan.layers_for_stage(stage))
    return jax.tree.map(lambda p: p[idx], layer_stack)


def assemble_layer_grads(plan, per_stage_grads):
    """Inverse of slice_stage_params over all stages: stitch the
    per-stage gradient trees (local chunk-major order) back into one
    natural-order [n_layers, ...] tree. Host-side test/driver helper."""
    import jax
    import jax.numpy as jnp

    order = np.concatenate(
        [np.asarray(plan.layers_for_stage(d)) for d in range(plan.S)])
    inv = np.argsort(order)
    return jax.tree.map(
        lambda *gs: jnp.concatenate(gs, axis=0)[inv], *per_stage_grads)


# ---------------------------------------------------------------------------
# Transport: double-buffered framed tensor exchange over the two rings
# ---------------------------------------------------------------------------


def _send_msg(sock, payload):
    sock.sendall(struct.pack("<Q", len(payload)) + payload)


def _recv_exact(sock, n, what):
    buf = bytearray()
    while len(buf) < n:
        try:
            chunk = sock.recv(min(1 << 20, n - len(buf)))
        except socket.timeout:
            raise MPMDTransferTimeout(
                "recv deadline expired waiting for %s (peer stage hung "
                "or dead — bounded by TPUFLOW_MPMD_RECV_TIMEOUT_S)" % what)
        if not chunk:
            raise MPMDTransferError(
                "peer closed mid-%s (stage died mid-transfer)" % what)
        buf.extend(chunk)
    return bytes(buf)


def _recv_msg(sock, what):
    (n,) = struct.unpack("<Q", _recv_exact(sock, 8, what))
    return _recv_exact(sock, n, what)


class _Closed(object):
    """Queue sentinel: the channel's thread exited with this error."""

    def __init__(self, error):
        self.error = error


class StageTransport(object):
    """Framed tensor exchange between stage gangs over the 1F1B rings.

    stage/world: this gang's pipeline coordinates. peers: host:port per
    stage (index = stage). Stage d dials (d+1)%S on the activation ring
    and (d-1)%S on the cotangent ring, and accepts the mirror-image
    inbound connections.

    double_buffer=True (default): serialization + the wire ride a
    background sender thread, and a background receiver thread prefetches
    inbound frames into a bounded queue — send/recv of microbatch k+1
    overlaps compute of microbatch k. False: every send and recv runs
    inline (the synchronous send-then-compute form).

    Wall-clock spent BLOCKED on the transport (inline send, queue put on
    a full buffer, recv wait) accumulates as transfer-stall time; the
    per-stage executor rides it into step telemetry so `tpuflow metrics`
    can show which stage is the bubble.
    """

    QUEUE_DEPTH = 8

    def __init__(self, stage, world, peers, double_buffer=True,
                 recv_timeout_s=None, send_timeout_s=None,
                 link_latency_ms=None):
        if world < 2:
            raise ValueError("StageTransport needs world >= 2")
        if len(peers) < world:
            raise ValueError(
                "MF_MPMD_PEERS lists %d addresses for %d stages"
                % (len(peers), world))
        self.stage, self.world = int(stage), int(world)
        self.peers = [_parse_addr(p) for p in peers[:world]]
        self.double_buffer = bool(double_buffer)
        self.recv_timeout_s = float(
            knobs.get_float("TPUFLOW_MPMD_RECV_TIMEOUT_S")
            if recv_timeout_s is None else recv_timeout_s)
        # sends tolerate backpressure (peer mid-compile, full prefetch
        # queue, genuine DCN latency) far longer than any liveness
        # signal: their deadline defaults to the recv deadline, never to
        # the 1s connect timeout. <= 0 means unbounded.
        self.send_timeout_s = float(
            knobs.get_float("TPUFLOW_MPMD_SEND_TIMEOUT_S",
                            fallback=self.recv_timeout_s)
            if send_timeout_s is None else send_timeout_s)
        self.link_latency_ms = float(
            knobs.get_float("TPUFLOW_MPMD_LINK_LATENCY_MS")
            if link_latency_ms is None else link_latency_ms)
        self._lock = threading.Lock()
        self._stats = {"frames_sent": 0, "frames_recv": 0,
                       "bytes_sent": 0, "bytes_recv": 0,
                       "stall_send_ms": 0.0, "stall_recv_ms": 0.0}
        self._out = {}      # chan -> socket
        self._in = {}       # chan -> socket
        self._send_q = {}   # chan -> Queue (double-buffered mode)
        self._recv_q = {}   # chan -> Queue (double-buffered mode)
        self._send_threads = []
        self._recv_threads = []
        self._send_error = {}
        self._closed = False
        self._listener = None

    # ---------- rendezvous ----------

    def start(self):
        """Bind this stage's address, dial both ring peers, accept the
        mirror-image inbound connections. Symmetric-dial safe: accepting
        runs on a thread while this thread dials."""
        host, port = self.peers[self.stage]
        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind((host, port))
        listener.listen(4)
        self._listener = listener
        connect_timeout = knobs.get_float(
            "TPUFLOW_MPMD_CONNECT_TIMEOUT_S")
        deadline = time.monotonic() + connect_timeout

        # inbound: activations from stage-1, cotangents from stage+1
        expect = {(CHAN_ACT, (self.stage - 1) % self.world),
                  (CHAN_COT, (self.stage + 1) % self.world)}
        accept_err = []

        def _accept():
            listener.settimeout(0.2)
            pending = dict.fromkeys(expect)
            while any(v is None for v in pending.values()):
                if time.monotonic() > deadline:
                    accept_err.append(MPMDTransferTimeout(
                        "stage %d: peers never connected: %s"
                        % (self.stage,
                           sorted(k for k, v in pending.items()
                                  if v is None))))
                    return
                try:
                    conn, _ = listener.accept()
                except socket.timeout:
                    continue
                # accepted sockets are BLOCKING (a listener's timeout
                # does not propagate): bound the hello read so one
                # stray/half-open connection cannot park the acceptor
                # past the rendezvous deadline. Real peers send the
                # hello immediately after connecting, so a short cap
                # keeps the acceptor servicing other inbound dials.
                conn.settimeout(
                    min(2.0, max(0.2, deadline - time.monotonic())))
                try:
                    hello = _recv_exact(conn, len(_HELLO) + 8, "hello")
                except MPMDTransferError:
                    conn.close()
                    continue
                if not hello.startswith(_HELLO):
                    conn.close()
                    continue
                rank, chan_id = struct.unpack_from("<II", hello, len(_HELLO))
                chan = CHAN_ACT if chan_id == 0 else CHAN_COT
                if (chan, rank) not in pending:
                    conn.close()
                    continue
                pending[(chan, rank)] = conn
                self._in[chan] = conn
            return

        acceptor = threading.Thread(target=_accept, daemon=True)
        acceptor.start()

        # outbound: activations to stage+1, cotangents to stage-1
        for chan, dst in ((CHAN_ACT, (self.stage + 1) % self.world),
                          (CHAN_COT, (self.stage - 1) % self.world)):
            self._out[chan] = self._dial(dst, chan, deadline)
        acceptor.join(timeout=connect_timeout + 1)
        if accept_err:
            raise accept_err[0]
        if len(self._in) != 2:
            raise MPMDTransferError(
                "stage %d: rendezvous incomplete (got channels %s)"
                % (self.stage, sorted(self._in)))
        # double-buffered: the receiver thread blocks on the socket
        # (peer death = EOF); the bounded deadline is enforced at the
        # consumer's queue.get. Synchronous: the deadline rides the
        # socket timeout of the inline read.
        for sock in self._in.values():
            sock.settimeout(None if self.double_buffer
                            else self.recv_timeout_s)
        if self.double_buffer:
            for chan in (CHAN_ACT, CHAN_COT):
                self._send_q[chan] = queue.Queue(maxsize=self.QUEUE_DEPTH)
                self._recv_q[chan] = queue.Queue(maxsize=self.QUEUE_DEPTH)
                t_s = threading.Thread(
                    target=self._sender_loop, args=(chan,), daemon=True)
                t_r = threading.Thread(
                    target=self._receiver_loop, args=(chan,), daemon=True)
                t_s.start()
                t_r.start()
                self._send_threads.append(t_s)
                self._recv_threads.append(t_r)
        return self

    def _dial(self, dst, chan, deadline):
        host, port = self.peers[dst]
        last = None
        while time.monotonic() < deadline:
            try:
                sock = socket.create_connection((host, port), timeout=1.0)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                sock.sendall(_HELLO + struct.pack(
                    "<II", self.stage, 0 if chan == CHAN_ACT else 1))
                # the 1s timeout above is a CONNECT timeout only — left
                # in place it would turn any >1s sendall backpressure
                # (peer mid-jit-compile, full prefetch queue, real DCN
                # latency) into a spurious peer-death verdict. Steady-
                # state sends get the generous send deadline instead.
                sock.settimeout(self.send_timeout_s
                                if self.send_timeout_s > 0 else None)
                return sock
            except OSError as exc:
                last = exc
                time.sleep(0.05)
        raise MPMDTransferTimeout(
            "stage %d: could not reach stage %d at %s:%d for %s ring: %s"
            % (self.stage, dst, host, port, chan, last))

    # ---------- the two data paths ----------

    def send(self, chan, arr, meta):
        """Ship one tensor down a ring. Journaled as the pinned
        `mpmd.send` collective (keyed by transfer identity) so a stage
        desync names the first diverging transfer; stall time is only
        the time THIS thread blocks (inline wire in synchronous mode,
        full-buffer backpressure in double-buffered mode)."""
        key = "%s:m%d:v%d" % (chan, meta.get("m", -1), meta.get("v", -1))
        sanitizer.journal_collective(
            "mpmd.send", axes=(chan,), shape=getattr(arr, "shape", None),
            key=key)
        t0 = time.perf_counter()
        if self.double_buffer:
            # bounded backpressure: a full queue is normal (that IS the
            # double-buffer), but the put must re-check the sender
            # thread's health each beat — if the thread died after an
            # initial check, an unbounded put would wedge this stage
            # forever, unreachable by the recv deadline.
            give_up = (time.monotonic() + self.send_timeout_s
                       if self.send_timeout_s > 0 else None)
            while True:
                err = self._send_error.get(chan)
                if err is not None:
                    raise err
                try:
                    self._send_q[chan].put((arr, dict(meta)), timeout=0.1)
                    break
                except queue.Full:
                    if give_up is not None and time.monotonic() > give_up:
                        raise MPMDTransferTimeout(
                            "stage %d: %s send queue full for %.1fs "
                            "(peer stage not draining — bounded by "
                            "TPUFLOW_MPMD_SEND_TIMEOUT_S)"
                            % (self.stage, chan, self.send_timeout_s))
        else:
            self._wire_send(chan, arr, meta)
        self._bump("stall_send_ms", (time.perf_counter() - t0) * 1e3)

    def recv(self, chan):
        """Pop the next frame off a ring: (meta, host_array). Blocking,
        but BOUNDED — the deadline expiring (peer hung) or the peer
        closing (peer died) raises instead of wedging this stage."""
        t0 = time.perf_counter()
        if self.double_buffer:
            try:
                item = self._recv_q[chan].get(timeout=self.recv_timeout_s)
            except queue.Empty:
                raise MPMDTransferTimeout(
                    "stage %d: no %s frame within %.1fs (peer stage hung "
                    "or dead)" % (self.stage, chan, self.recv_timeout_s))
            if isinstance(item, _Closed):
                # leave the sentinel for any later recv on this ring
                self._recv_q[chan].put(item)
                raise item.error
            meta, arr = item
        else:
            meta, arr = self._wire_recv(chan)
        self._bump("stall_recv_ms", (time.perf_counter() - t0) * 1e3)
        key = "%s:m%d:v%d" % (chan, meta.get("m", -1), meta.get("v", -1))
        sanitizer.journal_collective(
            "mpmd.recv", axes=(chan,), shape=arr.shape, key=key)
        return meta, arr

    def _wire_send(self, chan, arr, meta):
        payload = encode_frame(meta, arr)
        if self.link_latency_ms > 0:
            # modeled DCN latency: paid inline in synchronous mode,
            # hidden behind compute by the sender thread when buffered
            time.sleep(self.link_latency_ms / 1e3)
        try:
            _send_msg(self._out[chan], payload)
        except socket.timeout:
            raise MPMDTransferTimeout(
                "stage %d: %s send stalled past %.1fs (peer stage not "
                "draining — bounded by TPUFLOW_MPMD_SEND_TIMEOUT_S)"
                % (self.stage, chan, self.send_timeout_s))
        except OSError as exc:
            raise MPMDTransferError(
                "stage %d: %s send failed: %s" % (self.stage, chan, exc))
        self._bump("bytes_sent", len(payload))
        self._bump("frames_sent", 1)

    def _wire_recv(self, chan):
        data = _recv_msg(self._in[chan], "%s frame" % chan)
        self._bump("bytes_recv", len(data))
        self._bump("frames_recv", 1)
        return decode_frame(data)

    def _sender_loop(self, chan):
        q = self._send_q[chan]
        while True:
            item = q.get()
            if item is None:
                return
            arr, meta = item
            try:
                self._wire_send(chan, arr, meta)
            except MPMDTransferError as exc:
                self._send_error[chan] = exc
                return

    def _receiver_loop(self, chan):
        while True:
            try:
                item = self._wire_recv(chan)
            except (MPMDTransferError, OSError) as exc:
                if not self._closed:
                    err = (exc if isinstance(exc, MPMDTransferError)
                           else MPMDTransferError(str(exc)))
                    try:
                        self._recv_q[chan].put_nowait(_Closed(err))
                    except queue.Full:
                        pass
                return
            self._recv_q[chan].put(item)

    # ---------- accounting / lifecycle ----------

    def _bump(self, key, amount):
        with self._lock:
            self._stats[key] += amount

    def stats(self):
        with self._lock:
            out = dict(self._stats)
        out["stall_ms"] = out["stall_send_ms"] + out["stall_recv_ms"]
        out["double_buffer"] = self.double_buffer
        return out

    def close(self):
        self._closed = True
        # drain the senders first (in-flight frames still matter to the
        # peer's drain), then close the sockets — which is also what
        # unblocks receiver threads parked in a socket read
        for chan, q in self._send_q.items():
            try:
                q.put_nowait(None)
            except queue.Full:
                pass
        for t in self._send_threads:
            t.join(timeout=5)
        for sock in list(self._out.values()) + list(self._in.values()):
            try:
                sock.close()
            except OSError:
                pass
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        for t in self._recv_threads:
            t.join(timeout=2)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _parse_addr(addr):
    if isinstance(addr, (tuple, list)):
        return str(addr[0]), int(addr[1])
    host, _, port = str(addr).rpartition(":")
    return host or "127.0.0.1", int(port)


def peers_from_env():
    """Parse MF_MPMD_PEERS ("host:port,host:port,..." — index = stage)."""
    raw = os.environ.get("MF_MPMD_PEERS", "")
    return [p.strip() for p in raw.split(",") if p.strip()]


def transport_from_env(double_buffer=None, **kwargs):
    """Build the stage transport from the gang env: stage/world from
    MF_PARALLEL_NODE_INDEX/NUM_NODES, peer addresses from MF_MPMD_PEERS
    (exported by the local gang launch; external launchers pre-set it).
    TPUFLOW_MPMD_SYNC=1 forces the synchronous baseline transport."""
    peers = peers_from_env()
    if not peers:
        raise MPMDTransferError(
            "MF_MPMD_PEERS is not set — MPMD stage gangs need the peer "
            "rendezvous addresses the gang launch exports")
    if double_buffer is None:
        double_buffer = not knobs.get_bool("TPUFLOW_MPMD_SYNC")
    return StageTransport(
        stage=int(os.environ.get("MF_PARALLEL_NODE_INDEX", "0")),
        world=int(os.environ.get("MF_PARALLEL_NUM_NODES", str(len(peers)))),
        peers=peers, double_buffer=double_buffer, **kwargs)


# ---------------------------------------------------------------------------
# Per-stage executor: row `stage` of the schedule tables, as a host loop
# ---------------------------------------------------------------------------


class StageExecutor(object):
    """Execute one stage's row of the interleaved-1F1B timetable.

    Compiles exactly THREE programs for its chunk shape — chunk forward,
    mid-chunk backward (cotangent from the ring), last-chunk backward
    (loss + optional head grads) — with the virtual-stage index j a
    traced scalar (dynamic_index_in_dim into the [V, Lc, ...] stack),
    exactly like the SPMD switch branches. No stage ever traces another
    stage's program: that is the MPMD point.

    layer_fn: (carry, layer_params) -> carry, scanned over a chunk.
    loss_fn: (fp32_out, targets, head_params_or_None) -> scalar mean
        loss; only invoked on the last stage.
    return_input_grad: stage 0 collects dL/d(input) per microbatch so
        the caller can chain the embedding scatter-add transpose.
    """

    def __init__(self, plan, stage, transport, layer_fn, loss_fn=None,
                 return_input_grad=False):
        import jax
        import jax.numpy as jnp

        self.plan = plan
        self.stage = int(stage)
        self.transport = transport
        self.return_input_grad = bool(return_input_grad)
        self.is_first = self.stage == 0
        self.is_last = self.stage == plan.S - 1
        if self.is_last and loss_fn is None:
            raise ValueError("last stage needs loss_fn")

        def chunk_fwd(a, j, pv):
            pj = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(p, j, 0,
                                                       keepdims=False), pv)
            out, _ = jax.lax.scan(
                lambda c, lp: (layer_fn(c, lp), None), a, pj)
            return out

        def bwd_mid(a_sv, j, cot, pv):
            out, pullback = jax.vjp(
                lambda a, p: chunk_fwd(a, j, p), a_sv, pv)
            da, dp = pullback(cot.astype(out.dtype))
            return (da.astype(jnp.float32),
                    jax.tree.map(lambda g: g.astype(jnp.float32), dp))

        def bwd_last(a_sv, j, yb, pv, head):
            out, pullback = jax.vjp(
                lambda a, p: chunk_fwd(a, j, p), a_sv, pv)
            if head is None:
                loss_val, dldout = jax.value_and_grad(loss_fn)(
                    out.astype(jnp.float32), yb)
                dhead = None
            else:
                loss_val, (dldout, dhead) = jax.value_and_grad(
                    loss_fn, argnums=(0, 2)
                )(out.astype(jnp.float32), yb, head)
                dhead = jax.tree.map(
                    lambda g: g.astype(jnp.float32), dhead)
            da, dp = pullback(dldout.astype(out.dtype))
            return (loss_val, da.astype(jnp.float32),
                    jax.tree.map(lambda g: g.astype(jnp.float32), dp),
                    dhead)

        self._fwd = jax.jit(chunk_fwd)
        self._bwd_mid = jax.jit(bwd_mid)
        self._bwd_last = jax.jit(bwd_last)
        self.last_transfer_stall_ms = 0.0
        self._prev_stall_ms = None

    def compile_count(self):
        sizes = [f._cache_size() for f in
                 (self._fwd, self._bwd_mid, self._bwd_last)
                 if hasattr(f, "_cache_size")]
        return sum(sizes) if sizes else None

    def run(self, stage_params, x_mbs=None, y_mbs=None, head_params=None):
        """One full schedule pass (= one train step's loss/grad work).

        stage_params: [V*Lc, ...] stacked layer pytree in this stage's
            LOCAL order (slice_stage_params). x_mbs: [M, mb, ...]
            microbatched embedded inputs (stage 0 only). y_mbs:
            [M, mb, ...] targets (last stage only).
        Returns {"grads": [V*Lc,...] tree (/M, local order),
                 "loss": mean loss (last stage, else None),
                 "head_grads": (last stage w/ head, else None),
                 "input_grad": [M, mb, ...] fp32 (stage 0 w/
                     return_input_grad, else None)} and updates
        `last_transfer_stall_ms` with this pass's blocked wall-clock.
        """
        import jax
        import jax.numpy as jnp

        plan, d, T = self.plan, self.stage, self.plan.tables
        V, S, Lc, M = plan.V, plan.S, plan.Lc, plan.M
        VS = V * S
        if self.is_first and x_mbs is None:
            raise ValueError("stage 0 needs x_mbs (microbatched inputs)")
        if self.is_last and y_mbs is None:
            raise ValueError("last stage needs y_mbs (targets)")
        params_v = jax.tree.map(
            lambda p: p.reshape((V, Lc) + p.shape[1:]), stage_params)
        pgrads = jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), params_v)
        hgrads = (None if head_params is None else jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), head_params))
        loss = jnp.zeros((), jnp.float32)
        saved = [None] * max(1, int(T["n_saved"]))
        recv_f = [None] * max(1, int(T["n_recv_f"]))
        recv_b = [None] * max(1, int(T["n_recv_b"]))
        dx = [None] * M if (self.is_first and self.return_input_grad) \
            else None
        stall0 = self.transport.stats()["stall_ms"]

        for c in range(plan.n_cycles):
            # op first: same-cycle reads precede same-cycle stores,
            # exactly the SPMD cycle body's ordering
            if T["f_on"][d, c]:
                j = int(T["f_j"][d, c])
                m = int(T["f_m"][d, c])
                v = j * S + d
                if T["f_in"][d, c]:
                    a_in = x_mbs[m]
                else:
                    a_in = recv_f[int(T["f_rslot"][d, c])]
                saved[int(T["f_save"][d, c])] = a_in
                if v < VS - 1:
                    a_out = self._fwd(a_in, j, params_v)
                    self.transport.send(
                        CHAN_ACT, a_out, {"m": m, "v": v + 1, "c": c})
                # v == VS-1: the forward output is consumed by nobody —
                # the last-chunk backward recomputes from the saved
                # input (remat), so the compute is skipped here (the
                # SPMD program pays it only to stay in lockstep)
            elif T["b_on"][d, c]:
                j = int(T["b_j"][d, c])
                m = int(T["b_m"][d, c])
                v = j * S + d
                a_sv = saved[int(T["b_save"][d, c])]
                if T["b_last"][d, c]:
                    loss_val, da, dp, dhead = self._bwd_last(
                        a_sv, j, y_mbs[m], params_v, head_params)
                    loss = loss + loss_val
                    if dhead is not None:
                        hgrads = jax.tree.map(
                            lambda acc, g: acc + g, hgrads, dhead)
                else:
                    cot = recv_b[int(T["b_rslot"][d, c])]
                    da, dp = self._bwd_mid(a_sv, j, cot, params_v)
                pgrads = jax.tree.map(lambda acc, g: acc + g, pgrads, dp)
                if v > 0:
                    self.transport.send(
                        CHAN_COT, da, {"m": m, "v": v - 1, "c": c})
                if dx is not None and j == 0:
                    dx[m] = da

            # arrival-store directives: this cycle's inbound frames.
            # TCP order + cycle order reconstruct the slot mapping.
            fstore = int(T["fstore"][d, c])
            if fstore >= 0:
                _meta, arr = self.transport.recv(CHAN_ACT)
                recv_f[fstore] = jnp.asarray(arr)
            bstore = int(T["bstore"][d, c])
            if bstore >= 0:
                _meta, arr = self.transport.recv(CHAN_COT)
                recv_b[bstore] = jnp.asarray(arr)

        stall1 = self.transport.stats()["stall_ms"]
        self.last_transfer_stall_ms = round(stall1 - stall0, 3)
        grads = jax.tree.map(
            lambda g: (g / M).reshape((V * Lc,) + g.shape[2:]), pgrads)
        out = {"grads": grads, "loss": None, "head_grads": None,
               "input_grad": None}
        if self.is_last:
            out["loss"] = loss / M
            if hgrads is not None:
                out["head_grads"] = jax.tree.map(lambda g: g / M, hgrads)
        if dx is not None:
            # every microbatch's chunk-0 backward runs on stage 0, so
            # the schedule guarantees all M entries are populated
            out["input_grad"] = jnp.stack(dx) / M
        return out
