"""Disaggregated prefill/decode: the KV-handoff wire format.

Prefill and decode want different things from the device: prefill is a
compute-bound burst over the whole prompt, decode is a long
memory-bound drip of single tokens. Running both phases on every
replica makes each phase's tail latency hostage to the other's
occupancy (the Gemma-on-TPU serving comparison attributes its tail-
latency wins to splitting them; the Podracer architectures make the
same decoupling move for RL actors/learners over a shared store). In
`--prefill-workers K` mode, dedicated prefill replicas run ONLY chunked
prefill (`POST /v1/prefill` -> Request(prefill_only=True)), then the
router ships the finished KV state to a decode replica
(`POST /v1/decode` -> Request(prefilled=...)), which seeds its slot
view and continues from the first token. SlotEngine.extract_kv /
admit_prefilled are the two ends of the pipe.

This module is the pipe itself: a self-describing binary frame —
  MAGIC | u32 header length | JSON header | raw k bytes | raw v bytes
— where the header carries the array dtype/shapes plus arbitrary JSON
metadata (the original request payload, the first sampled token). Raw
buffers rather than npz because the KV dtype may be bfloat16
(ml_dtypes), which numpy's save path does not round-trip reliably.

A model that carries recurrent state (state-space layers) has no such
handoff yet: its K and V are not the whole of what a prefill leaves, and
the scheduler refuses its prefill_only / prefilled requests by name
(engine.refuse_recurrent).

Identity: the handed-off KV is bitwise what the decode replica's own
prefill would have written, and the decode side resumes the request's
rng key schedule at cursor 1, so the disaggregated path emits exactly
the tokens a unified replica would (pinned by tests).
"""

import json
import struct

import ml_dtypes  # noqa: F401  (registers bfloat16 & friends with numpy)
import numpy as np

MAGIC = b"TPFKV1\n"




def encode_handoff(meta, kv):
    """Frame a KV handoff: `meta` is JSON-safe metadata, `kv` is
    {"k": [layers, T, kv_heads, head_dim], "v": ...} host arrays, as
    SlotEngine.extract_kv gave them (`layers` is the pool's leading
    axis: passes x layers for a stack that is run several times; the
    header carries the shapes, so the frame takes either)."""
    k = np.ascontiguousarray(kv["k"])
    v = np.ascontiguousarray(kv["v"])
    header = dict(meta)
    header["dtype"] = str(k.dtype)
    header["k_shape"] = list(k.shape)
    header["v_shape"] = list(v.shape)
    hb = json.dumps(header).encode("utf-8")
    return b"".join([MAGIC, struct.pack("<I", len(hb)), hb,
                     k.tobytes(), v.tobytes()])


def decode_handoff(data):
    """Inverse of encode_handoff: returns (meta, {"k": ..., "v": ...})."""
    if not data.startswith(MAGIC):
        raise ValueError("not a KV handoff frame")
    off = len(MAGIC)
    (hlen,) = struct.unpack_from("<I", data, off)
    off += 4
    header = json.loads(data[off:off + hlen].decode("utf-8"))
    off += hlen
    dtype = np.dtype(header.pop("dtype"))
    k_shape = tuple(header.pop("k_shape"))
    v_shape = tuple(header.pop("v_shape"))
    k_bytes = int(np.prod(k_shape)) * dtype.itemsize
    v_bytes = int(np.prod(v_shape)) * dtype.itemsize
    if len(data) != off + k_bytes + v_bytes:
        raise ValueError("KV handoff frame truncated")
    k = np.frombuffer(data, dtype, count=int(np.prod(k_shape)),
                      offset=off).reshape(k_shape)
    v = np.frombuffer(data, dtype, count=int(np.prod(v_shape)),
                      offset=off + k_bytes).reshape(v_shape)
    return header, {"k": k, "v": v}
