"""Rollout wire-format codec: host pytrees ↔ ``Rollout`` protos.

The reference shipped experience as protobuf payloads over RabbitMQ but left
the payload schema implicit (SURVEY.md §2.1 "Transport", §7 step 1); here it
is first-party: a flat ``name → TensorProto`` map whose names are the
slash-joined paths of the training-batch pytree (``obs/units``,
``actions/move_x``, ``carry0/h``, ...). The same codec serves the learner→
actor weights direction (``ModelWeights``).

Decode is the hot ingestion path; ``decode_rollout_bytes`` uses the
first-party C++ wire parser (``dotaclient_tpu/native/rollout_codec.cc``,
single pass, zero-copy numpy views) when the native library is built, with
a pure-protobuf fallback otherwise (SURVEY.md §2.2 row 3).
"""

from __future__ import annotations

import ctypes
import threading
import zlib
from typing import Any, Dict, List, Mapping, Tuple

import ml_dtypes
import numpy as np

from dotaclient_tpu.protos import dota_pb2 as pb

# bfloat16 arrays cross the wire when actors run bf16 inference
_BFLOAT16 = np.dtype(ml_dtypes.bfloat16)


def _np_dtype(name: str) -> np.dtype:
    if name == "bfloat16":
        return _BFLOAT16
    return np.dtype(name)


def _dtype_name(dtype: np.dtype) -> str:
    if dtype == _BFLOAT16:
        return "bfloat16"
    return dtype.name


# -- wire-frame integrity (ISSUE 4) ------------------------------------------
#
# Every rollout/weights frame on the socket and shm lanes carries a 4-byte
# CRC32 trailer so both readers can drop (and count) corrupt frames instead
# of feeding garbage to the decoder or crashing the reader thread. Computing
# byte-serial zlib CRC over large frames would dominate the zero-copy shm
# drain (~1 GiB/s vs the ~8 GiB/s ring memcpy on this host), so frames
# larger than _CRC_FOLD_THRESHOLD are first folded to a 64-bit digest with a
# vectorized XOR over 8-byte lanes (memory-bandwidth speed, measured ~11
# GiB/s even unaligned) and the CRC32 covers (digest || unaligned tail).
# Detection: any single-bit flip, any torn/partial write, and any burst
# shorter than 8 bytes changes the digest; the only blind spot is a
# corruption pattern that repeats identically at the same lane offset in an
# even number of words — vanishingly unlikely for real wire/DMA faults.
# Small frames (heartbeats, control, short rollouts) get plain CRC32.

FRAME_CRC = zlib.crc32  # exposed for tests asserting the small-frame path
_CRC_FOLD_THRESHOLD = 4096
CRC_SIZE = 4


def frame_crc32(payload) -> int:
    """32-bit integrity trailer for one wire frame (bytes-like, zero-copy:
    memoryview slices fold in place)."""
    n = len(payload)
    if n <= _CRC_FOLD_THRESHOLD:
        return zlib.crc32(payload) & 0xFFFFFFFF
    m = n & ~7
    fold = int(
        np.bitwise_xor.reduce(np.frombuffer(payload, "<u8", count=m >> 3))
    )
    c = zlib.crc32(fold.to_bytes(8, "little"), n & 0xFFFFFFFF)
    if m != n:
        c = zlib.crc32(payload[m:], c)
    return c & 0xFFFFFFFF


def flatten_tree(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    """Flatten a nested dict/tuple pytree of arrays to slash-joined names."""
    out: Dict[str, np.ndarray] = {}
    if isinstance(tree, Mapping):
        items = tree.items()
    elif isinstance(tree, (tuple, list)):
        items = ((str(i), v) for i, v in enumerate(tree))
    else:
        # array-likes (numpy AND device arrays) pass through untouched:
        # np.asarray on a device array is a per-leaf host↔device sync —
        # encode_weights batches its fetch over the whole tree instead
        # (ISSUE 5); plain scalars/lists still materialize here
        out[prefix.rstrip("/")] = (
            tree
            if hasattr(tree, "dtype") and hasattr(tree, "shape")
            else np.asarray(tree)
        )
        return out
    for k, v in items:
        out.update(flatten_tree(v, f"{prefix}{k}/"))
    return out


def unflatten_tree(flat: Mapping[str, np.ndarray]) -> Dict[str, Any]:
    """Inverse of :func:`flatten_tree` (all-numeric levels become tuples)."""
    nested: Dict[str, Any] = {}
    for name, value in flat.items():
        parts = name.split("/")
        node = nested
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value

    def fix(node):
        if not isinstance(node, dict):
            return node
        # tuple levels are exactly what flatten_tree emits: UNPADDED
        # indices 0..n-1. Zero-padded digit keys ("00", "01" — e.g. the
        # outcome plane's histogram bucket names riding a fleet snapshot)
        # are ordinary dict keys, not tuple indices; treating them as
        # indices KeyError'd the whole decode (ISSUE 15 bugfix sweep).
        if node and all(
            k.isdigit() and str(int(k)) == k for k in node
        ) and set(node) == {str(i) for i in range(len(node))}:
            return tuple(fix(node[str(i)]) for i in range(len(node)))
        return {k: fix(v) for k, v in node.items()}

    return fix(nested)


# -- rollout wire narrowing (ISSUE 7) -----------------------------------------
#
# The experience stream is the dominant byte flow at scale: every actor ships
# one encoded chunk per finished lane, and PR 3's bf16 weights discipline left
# rollout payloads full-width f32. ``TransportConfig.rollout_wire_dtype``
# extends the same in-band ``__wire_cast__`` marker discipline to rollouts:
# f32 observation/feature leaves narrow to bf16 at encode, bounded
# integer-like leaves (action indices, hero ids — the producer's config
# bounds their range) narrow to int8/int16 where the cast is exact, and the
# marker entry names exactly what was narrowed (``name=orig_dtype`` lines).
# Decode keeps the narrow dtypes by default — the trajectory buffer stores
# them narrow and upcasts on-device at consume time — and ``upcast=True``
# restores the original dtypes on host (bf16→f32 and int8→int32 are exact,
# so the restored batch is bit-identical to what an f32 wire would have
# carried for bf16-representable inputs).
#
# Precision-critical leaves are PINNED f32 by the allowlist below and cross
# the wire byte-identical: behavior_logp feeds the PPO importance ratio
# exp(logp - behavior_logp) where bf16's 8 mantissa bits would inject
# O(0.4%) multiplicative noise into every surrogate term; rewards/values
# accumulate over the GAE scan (quantization noise compounds across T);
# dones gates the recursion; the LSTM initial carries (carry0/*) seed the
# whole sequence forward.

ROLLOUT_WIRE_DTYPES = ("float32", "bfloat16")
_ROLLOUT_PINNED_NAMES = frozenset(
    {"behavior_logp", "rewards", "dones", "values"}
)
_ROLLOUT_PINNED_PREFIXES = ("carry0/",)


def rollout_leaf_pinned(name: str) -> bool:
    """True iff this rollout leaf must cross the wire at full width."""
    return name in _ROLLOUT_PINNED_NAMES or name.startswith(
        _ROLLOUT_PINNED_PREFIXES
    )


def rollout_int_bounds(config) -> Dict[str, int]:
    """Max values the producer's config guarantees for integer-like rollout
    leaves — the input that licenses exact int8/int16 narrowing. Computed
    from the SAME RunConfig on both ends (actor encode, learner buffer
    template), so the dtypes agree wherever the configs do (the buffer's
    skew check already requires that)."""
    bounds = {
        f"actions/{head}": size - 1
        for head, size in config.actions.head_sizes.items()
    }
    bounds["obs/hero_id"] = config.model.n_hero_ids - 1
    # Unit handles are sim-assigned identities: the vectorized/device sims
    # use slot permutations (≤ max_units), the scalar sim increments per
    # spawn (~hundreds over a 600 s game). int16 is exact for both with
    # orders of magnitude of headroom, and the encode path VERIFIES the
    # range before casting (a handle source that ever outgrew the bound
    # fails loudly instead of wrapping).
    bounds["obs/unit_handles"] = np.iinfo(np.int16).max
    return bounds


def decode_drained_payloads(
    payloads, tel, totals: List[int]
) -> "Tuple[list, int]":
    """Decode a transport drain's wire payloads with the SHARED wire/raw
    byte accounting (ISSUE 7) — the one copy of the accounting both the
    socket and shm consume paths run, so the ``--require-wire`` telemetry
    can never diverge between lanes. ``totals`` is the server's mutable
    ``[wire_total, raw_total]`` pair (updated in place). Returns
    ``(decoded (meta, arrays) pairs, malformed-payload count)`` —
    malformed payloads (version-skewed actors, port scanners) are counted
    and dropped, the disposable-actor failure model (SURVEY.md §5.3).

    Items may be bare payloads or ``(recv_ts, payload)`` pairs — both
    transports ship the pair (ISSUE 12: the receive timestamp is the
    ``recv`` trace hop; receive and CRC verify share it, both lanes
    verify in the same pass). Trace stamping runs ONLY when this process
    has a tracer configured — an untracing learner pays one pointer test
    per drain."""
    from dotaclient_tpu.utils import tracing

    tracer = tracing.get()
    out = []
    bad = 0
    wire = raw = 0
    for item in payloads:
        recv_ts, p = item if isinstance(item, tuple) else (None, item)
        try:
            meta, arrays = decode_rollout_bytes(p)
        except Exception:
            bad += 1
            continue
        if tracer is not None and "trace_blob" in meta:
            tracing.stamp_wire_hops(meta, recv_ts)
        # actual bytes consumed vs what the same payloads would have cost
        # full-width — the decoder computed both from the in-band cast
        # marker (host ints only)
        wire += meta.get("wire_bytes", len(p))
        raw += meta.get("raw_bytes", len(p))
        out.append((meta, arrays))
    if out:
        totals[0] += wire
        totals[1] += raw
        tel.counter("transport/rollout_bytes_total").inc(wire)
        tel.counter("transport/rollout_raw_bytes_total").inc(raw)
        if totals[0]:   # zero-length payloads leave the gauge at its floor
            tel.gauge("transport/rollout_compression_ratio").set(
                totals[1] / totals[0]
            )
    return out, bad


def rollout_wire_kwargs(config) -> Dict[str, Any]:
    """The encode-call kwargs this config's rollout wire needs — ``{}``
    for a full-width wire. The ONE derivation every encoder shares
    (both actor pools): a change to the encode contract (a new bound
    source, say) lands here once instead of drifting across hand-rolled
    copies."""
    if config.transport.rollout_wire_dtype == "float32":
        return {}
    return dict(
        wire_dtype=config.transport.rollout_wire_dtype,
        int_bounds=rollout_int_bounds(config),
    )


def rollout_cast_plan(
    specs: Mapping[str, Any],
    wire_dtype: str,
    int_bounds: "Mapping[str, int] | None" = None,
) -> Dict[str, np.dtype]:
    """``leaf name → narrow dtype`` for the leaves that change on the wire.

    ``specs`` maps flat leaf names to dtypes (anything ``np.dtype``
    accepts). Only f32 leaves off the pinned allowlist narrow to bf16;
    signed-integer leaves narrow to int8/int16 only when ``int_bounds``
    names them with a config-guaranteed max value that fits — exact by
    construction, never value-sniffed (a value-dependent plan would make
    one actor's chunks dtype-unstable and trip the buffer's skew check).
    """
    if wire_dtype not in ROLLOUT_WIRE_DTYPES:
        raise ValueError(
            f"unknown rollout_wire_dtype {wire_dtype!r} "
            f"(expected one of {ROLLOUT_WIRE_DTYPES})"
        )
    if wire_dtype == "float32":
        return {}
    plan: Dict[str, np.dtype] = {}
    for name, dtype in specs.items():
        dtype = np.dtype(dtype)
        if rollout_leaf_pinned(name):
            continue
        if dtype == np.float32:
            plan[name] = _BFLOAT16
        elif dtype.kind == "i" and int_bounds and name in int_bounds:
            bound = int(int_bounds[name])
            if 0 <= bound <= np.iinfo(np.int8).max and dtype.itemsize > 1:
                plan[name] = np.dtype(np.int8)
            elif 0 <= bound <= np.iinfo(np.int16).max and dtype.itemsize > 2:
                plan[name] = np.dtype(np.int16)
    return plan


def apply_cast_plan(
    flat: Mapping[str, Any], plan: "Mapping[str, np.dtype]"
) -> Dict[str, Any]:
    """Apply a :func:`rollout_cast_plan` to a flat leaf dict — the ONE
    place the cast lands. The host encode path, the buffer's narrow
    template, and the device collect program all route through here, so a
    new narrowed kind changes dtype in lockstep at every site (three
    hand-rolled copies would let the actor, ring, and wire silently
    disagree and trip the buffer's skew check). Works on numpy arrays and
    jax tracers alike (both carry ``astype``)."""
    return {
        n: (a.astype(plan[n]) if n in plan else a) for n, a in flat.items()
    }


_CAST_PLAN_CACHE: Dict[tuple, tuple] = {}


def _narrow_rollout_flat(
    flat: Dict[str, Any],
    wire_dtype: str,
    int_bounds: "Mapping[str, int] | None",
) -> "Tuple[Dict[str, Any], bytes | None]":
    """Apply the cast plan to a flat leaf dict; returns ``(flat', marker
    blob)`` where the blob is the newline-joined ``name=orig_dtype`` record
    the decoder needs to restore the original dtypes (None when nothing
    narrowed — an f32 wire carries no marker).

    The plan and marker are pure functions of (leaf names, dtypes,
    wire_dtype, bounds) and rollout structure is fixed across an actor's
    lifetime (the ``_SPEC_CACHE`` premise), so both are memoized — the
    per-chunk ship path pays only the int range verification and the
    casts themselves."""
    if wire_dtype == "float32":
        # feature off (the default): skip even the memo-key build — this
        # is every actor's per-chunk ship path
        return flat, None
    key = (
        tuple((n, _dtype_name(np.dtype(a.dtype))) for n, a in flat.items()),
        wire_dtype,
        tuple(sorted(int_bounds.items())) if int_bounds else None,
    )
    cached = _CAST_PLAN_CACHE.get(key)
    if cached is None:
        plan = rollout_cast_plan(
            {n: a.dtype for n, a in flat.items()}, wire_dtype, int_bounds
        )
        marker = (
            "\n".join(
                f"{name}={_dtype_name(np.dtype(flat[name].dtype))}"
                for name in plan
            ).encode()
            if plan
            else None
        )
        _CAST_PLAN_CACHE[key] = cached = (plan, marker)
    plan, marker = cached
    if not plan:
        return flat, None
    for name, narrow in plan.items():
        arr = flat[name]
        if np.dtype(narrow).kind == "i" and isinstance(arr, np.ndarray):
            # exactness guard: the int bound is a config PROMISE — verify
            # it on the host path before a silent wrap could corrupt the
            # stream (the device path casts in-graph and relies on the
            # sim's by-construction bounds)
            info = np.iinfo(narrow)
            if arr.size and (
                arr.min() < info.min or arr.max() > info.max
            ):
                raise ValueError(
                    f"rollout leaf {name!r} exceeds its declared int bound "
                    f"({info.max}): observed range "
                    f"[{arr.min()}, {arr.max()}] does not fit {info.dtype} "
                    f"— fix rollout_int_bounds or widen the cast"
                )
    return apply_cast_plan(flat, plan), marker


def _parse_cast_marker(blob: bytes) -> Dict[str, str]:
    """Marker blob → ``{leaf name: original dtype name}``."""
    cast: Dict[str, str] = {}
    for line in blob.decode().split("\n"):
        if not line:
            continue
        name, _, orig = line.partition("=")
        cast[name] = orig
    return cast


def _upcast_flat(
    flat: Dict[str, np.ndarray], cast: Mapping[str, str]
) -> Dict[str, np.ndarray]:
    """Restore narrowed leaves to their original dtypes (exact: every bf16
    value is representable in f32, every int8/int16 in int32)."""
    for name, orig in cast.items():
        arr = flat.get(name)
        if arr is not None:
            flat[name] = arr.astype(_np_dtype(orig))
    return flat


def tensor_to_proto(arr: np.ndarray) -> pb.TensorProto:
    arr = np.ascontiguousarray(arr)
    return pb.TensorProto(
        shape=list(arr.shape), dtype=_dtype_name(arr.dtype), data=arr.tobytes()
    )


def proto_to_tensor(t: pb.TensorProto) -> np.ndarray:
    arr = np.frombuffer(t.data, dtype=_np_dtype(t.dtype))
    return arr.reshape(tuple(t.shape)).copy()


def encode_rollout(
    arrays: Any,
    model_version: int,
    env_id: int,
    rollout_id: int,
    length: int,
    total_reward: float,
    wire_dtype: str = "float32",
    int_bounds: "Mapping[str, int] | None" = None,
    trace: "bytes | None" = None,
) -> pb.Rollout:
    """Serialize one rollout's pytree of host arrays.

    ``wire_dtype="bfloat16"`` narrows the experience leaves per
    :func:`rollout_cast_plan` (pinned leaves stay byte-identical f32) and
    records the casts in the in-band ``__wire_cast__`` marker entry.
    ``trace`` (ISSUE 12) is a pipeline-tracing record blob
    (``utils/tracing.record_to_blob``) that rides as one more in-band
    marker entry (``__trace__``) on sampled chunks."""
    r = pb.Rollout(
        model_version=model_version,
        env_id=env_id,
        rollout_id=rollout_id,
        length=length,
        total_reward=total_reward,
    )
    flat = flatten_tree(arrays)
    flat, marker = _narrow_rollout_flat(flat, wire_dtype, int_bounds)
    n_entries = (
        len(flat)
        + (1 if marker is not None else 0)
        + (1 if trace is not None else 0)
    )
    if n_entries > _MAX_TENSORS:
        _raise_too_many_tensors(n_entries, "encode")
    for name, arr in flat.items():
        r.arrays[name].CopyFrom(tensor_to_proto(arr))
    if marker is not None:
        r.arrays[_WIRE_CAST_MARKER].CopyFrom(
            pb.TensorProto(shape=[len(marker)], dtype="marker", data=marker)
        )
    if trace is not None:
        r.arrays[_TRACE_MARKER].CopyFrom(
            pb.TensorProto(shape=[len(trace)], dtype="marker", data=trace)
        )
    return r


def decode_rollout(
    r: pb.Rollout, upcast: bool = False
) -> Tuple[Dict[str, Any], Any]:
    """Deserialize → (meta dict, pytree of arrays).

    Narrowed leaves come back in their WIRE dtypes by default (the
    trajectory buffer stores them narrow and upcasts on-device at consume
    time); the marker record lands in ``meta["wire_cast"]``. ``upcast=True``
    restores the original dtypes on host (tests, non-buffer consumers)."""
    meta = {
        "model_version": r.model_version,
        "env_id": r.env_id,
        "rollout_id": r.rollout_id,
        "length": r.length,
        "total_reward": r.total_reward,
    }
    flat = {}
    cast: Dict[str, str] = {}
    for name, t in r.arrays.items():
        if name == _WIRE_CAST_MARKER:
            cast = _parse_cast_marker(t.data)
            continue
        if name == _TRACE_MARKER:
            meta["trace_blob"] = t.data
            continue
        flat[name] = proto_to_tensor(t)
    if cast:
        meta["wire_cast"] = cast
        if upcast:
            flat = _upcast_flat(flat, cast)
    return meta, unflatten_tree(flat)


_MAX_TENSORS = 64
# structured view over the C TensorEntry array — field access is vectorized
# numpy instead of per-attribute ctypes getattr
_ENTRY_DTYPE = np.dtype(
    [
        ("name_off", "<u4"), ("name_len", "<u4"),
        ("dtype_off", "<u4"), ("dtype_len", "<u4"),
        ("data_off", "<u4"), ("data_len", "<u4"),
        ("shape", "<i4", (8,)), ("ndim", "<i4"),
    ]
)
# encoder-side mirror of the C EncodeTensor struct (align=True matches the
# C++ compiler's layout; asserted against ctypes.sizeof at first use)
_ENC_DTYPE = np.dtype(
    [
        ("name_off", "<u4"), ("name_len", "<u4"),
        ("dtype_off", "<u4"), ("dtype_len", "<u4"),
        ("data_ptr", "<u8"), ("data_len", "<u8"),
        ("shape", "<i4", (8,)), ("ndim", "<i4"),
    ],
    align=True,
)
_DTYPE_CACHE: Dict[bytes, np.dtype] = {}
_SPEC_CACHE: Dict[tuple, tuple] = {}
_tls = threading.local()


def _entry_buffer():
    buf = getattr(_tls, "entries", None)
    if buf is None:
        buf = np.zeros(_MAX_TENSORS, _ENTRY_DTYPE)
        _tls.entries = buf
    return buf


def _raise_too_many_tensors(n_entries: int, side: str) -> None:
    raise ValueError(
        f"rollout payload carries {n_entries} tensor entries at {side}; the "
        f"native wire codec's entry table holds at most {_MAX_TENSORS} — a "
        f"silent fallback here would walk a truncated entry buffer (decode) "
        f"or pin the learner to the slow proto parser forever (encode). "
        f"Flatten fewer leaves or raise _MAX_TENSORS in "
        f"transport/serialize.py"
    )


def decode_rollout_bytes(
    payload: bytes, native: bool = True, upcast: bool = False
) -> Tuple[Dict[str, Any], Any]:
    """Decode a serialized ``Rollout`` from raw bytes.

    The learner-ingest fast path: with the native library built (see
    ``dotaclient_tpu.native``), one C pass locates every tensor and the
    arrays are materialized as zero-copy ``np.frombuffer`` views into
    ``payload``; otherwise falls back to python-protobuf. ``payload`` may
    be bytes OR a read-only buffer (the shm lane hands memoryview slices
    of its drain snapshots — no copy on the way in either). Views are
    read-only — callers that mutate must copy (the trajectory buffer only
    uploads, so the hot path never does).

    Wire-narrowed payloads (``rollout_wire_dtype``, ISSUE 7) decode to
    their NARROW dtypes by default — the trajectory buffer keeps them
    narrow and the upcast happens on-device at consume time. The marker
    record lands in ``meta["wire_cast"]`` and the byte accounting in
    ``meta["wire_bytes"]`` / ``meta["raw_bytes"]`` (what the same payload
    would have cost full-width — the transports' compression telemetry).
    ``upcast=True`` restores original dtypes on host (a copy; tests and
    non-buffer consumers).

    A payload with more tensor entries than the native table holds raises
    ``ValueError`` naming the count (the transports' consume paths count
    it as a bad payload) — never a silent fall-through that would leave a
    truncated entry walk or a permanent slow-path downgrade.
    """
    if not isinstance(payload, (bytes, bytearray, memoryview)):
        payload = bytes(payload)  # exotic bytes-like in
    if native:
        from dotaclient_tpu.native.build import (
            RolloutHeader,
            TensorEntry,
            load_library,
        )

        lib = load_library()
        if lib is not None:
            if isinstance(payload, bytes):
                src = payload          # c_void_p accepts bytes directly
            else:
                # raw pointer into the buffer — kept alive by `payload`
                src = ctypes.c_void_p(
                    np.frombuffer(payload, np.uint8).ctypes.data
                )
            hdr = RolloutHeader()
            entries = _entry_buffer()
            n = lib.dota_decode_rollout(
                src, len(payload), ctypes.byref(hdr),
                entries.ctypes.data_as(ctypes.POINTER(TensorEntry)),
                _MAX_TENSORS,
            )
            if n == -2:
                # entry-table overflow: loud, with the real count (the
                # payload is well-formed proto — count it; if it is NOT
                # parseable either, fall through to the proto path's own
                # parse error)
                try:
                    r = pb.Rollout()
                    r.ParseFromString(bytes(payload))
                except Exception:
                    pass
                else:
                    _raise_too_many_tensors(len(r.arrays), "decode")
            if n >= 0:
                flat = {}
                cast: Dict[str, str] = {}
                trace_blob: "bytes | None" = None
                # one C-level conversion: rows become plain python tuples
                for (
                    name_off, name_len, dtype_off, dtype_len,
                    data_off, data_len, shape, ndim,
                ) in entries[:n].tolist():
                    name = bytes(payload[name_off:name_off + name_len]).decode()
                    if name == _WIRE_CAST_MARKER:
                        cast = _parse_cast_marker(
                            bytes(payload[data_off:data_off + data_len])
                        )
                        continue
                    if name == _TRACE_MARKER:
                        trace_blob = bytes(
                            payload[data_off:data_off + data_len]
                        )
                        continue
                    dkey = bytes(payload[dtype_off:dtype_off + dtype_len])
                    dtype = _DTYPE_CACHE.get(dkey)
                    if dtype is None:
                        dtype = _np_dtype(dkey.decode())
                        _DTYPE_CACHE[dkey] = dtype
                    count = data_len // dtype.itemsize
                    arr = np.frombuffer(
                        payload, dtype=dtype, count=count, offset=data_off
                    )
                    if ndim != 1 or shape[0] != count:
                        arr = arr.reshape(shape[:ndim])
                    flat[name] = arr
                meta = {
                    "model_version": hdr.model_version,
                    "env_id": hdr.env_id,
                    "rollout_id": hdr.rollout_id,
                    "length": hdr.length,
                    "total_reward": hdr.total_reward,
                }
                if trace_blob is not None:
                    meta["trace_blob"] = trace_blob
                if cast:
                    # narrowed payloads carry their byte accounting; plain
                    # f32 frames keep the historical meta shape exactly
                    # (consume telemetry falls back to len(payload))
                    meta["wire_cast"] = cast
                    _attach_wire_accounting(meta, flat, cast, len(payload))
                    if upcast:
                        flat = _upcast_flat(flat, cast)
                return meta, unflatten_tree(flat)
            # n == -1 (malformed): fall through to the proto parser
    r = pb.Rollout()
    r.ParseFromString(
        payload if isinstance(payload, bytes) else bytes(payload)
    )
    if len(r.arrays) > _MAX_TENSORS:
        _raise_too_many_tensors(len(r.arrays), "decode")
    meta, arrays = decode_rollout(r, upcast=upcast)
    if meta.get("wire_cast"):
        meta["wire_bytes"] = len(payload)
        raw = len(payload) - _wire_cast_overhead(meta["wire_cast"])
        for name, orig in meta["wire_cast"].items():
            # `in` before indexing: protobuf map __getitem__ auto-inserts
            if name in r.arrays:
                t = r.arrays[name]
                raw += _leaf_raw_delta(
                    name, tuple(t.shape), _np_dtype(t.dtype), orig
                )
        meta["raw_bytes"] = raw
    return meta, arrays


def _varint_size(n: int) -> int:
    """Bytes a proto3 varint of ``n`` occupies."""
    size = 1
    while n > 0x7F:
        n >>= 7
        size += 1
    return size


def _wire_cast_overhead(cast: Mapping[str, str]) -> int:
    """Exact wire footprint of the ``__wire_cast__`` marker map entry —
    an f32 payload carries NO marker, so ``raw_bytes`` must exclude it or
    the compression ratio overstates the saving (~0.4% on the default
    config). Both codecs emit canonical proto3 (asserted equal in tests),
    so the size is computable from the blob length alone: TensorProto
    {shape=[blob_len] packed, dtype="marker", data=blob} wrapped in a map
    entry wrapped in Rollout field 6 (all tags are one byte)."""
    blob_len = sum(
        len(n) + 1 + len(o) for n, o in cast.items()
    ) + max(0, len(cast) - 1)   # name=orig lines, newline-joined
    packed = _varint_size(blob_len)
    tensor = (
        1 + _varint_size(packed) + packed
        + 1 + 1 + len("marker")
        + 1 + _varint_size(blob_len) + blob_len
    )
    key = len(_WIRE_CAST_MARKER)
    entry = 1 + _varint_size(key) + key + 1 + _varint_size(tensor) + tensor
    return 1 + _varint_size(entry) + entry


def _leaf_raw_delta(
    name: str, shape, narrow: np.dtype, orig_name: str
) -> int:
    """Exact wire-byte difference between this leaf's full-width and
    narrow map entries: the data blob halves, but the dtype STRING also
    changes length ("bfloat16" vs "float32" is +1, "int8" vs "int32" is
    -1) and every length varint can change width — sub-byte effects that
    would otherwise leave raw_bytes a few bytes off per payload."""
    n = 1
    for d in shape:
        n *= int(d)
    packed = sum(_varint_size(int(d)) for d in shape)
    shape_field = (
        (1 + _varint_size(packed) + packed) if len(shape) else 0
    )
    key = len(name)

    def entry_total(dtype_name: str, itemsize: int) -> int:
        dlen = n * itemsize
        ds = len(dtype_name)
        tensor = (
            shape_field
            + 1 + _varint_size(ds) + ds
            + 1 + _varint_size(dlen) + dlen
        )
        e = 1 + _varint_size(key) + key + 1 + _varint_size(tensor) + tensor
        return 1 + _varint_size(e) + e

    return entry_total(orig_name, _np_dtype(orig_name).itemsize) - (
        entry_total(_dtype_name(narrow), narrow.itemsize)
    )


def _attach_wire_accounting(
    meta: Dict[str, Any],
    flat: Mapping[str, np.ndarray],
    cast: Mapping[str, str],
    wire_bytes: int,
) -> None:
    """Record per-payload byte accounting: actual wire bytes and what the
    same payload would have cost full-width — EXACTLY: the marker entry
    exists only on the narrow wire (excluded from ``raw``), and each
    narrowed leaf's framing is re-costed at its original dtype
    (:func:`_leaf_raw_delta`). Pinned by a test asserting raw_bytes
    equals the true f32 encode's length byte-for-byte."""
    meta["wire_bytes"] = wire_bytes
    raw = wire_bytes - _wire_cast_overhead(cast)
    for name, orig in cast.items():
        arr = flat.get(name)
        if arr is not None:
            raw += _leaf_raw_delta(name, arr.shape, arr.dtype, orig)
    meta["raw_bytes"] = raw


def encode_rollout_bytes(
    arrays: Any,
    model_version: int,
    env_id: int,
    rollout_id: int,
    length: int,
    total_reward: float,
    native: bool = True,
    wire_dtype: str = "float32",
    int_bounds: "Mapping[str, int] | None" = None,
    trace: "bytes | None" = None,
) -> "bytes | memoryview":
    """Serialize one rollout straight to wire bytes (bytes-like).

    The actor-ship fast path, mirror of :func:`decode_rollout_bytes`: with
    the native library built, one C pass writes the proto3 wire format
    directly from the numpy buffers (one memcpy per tensor, no
    python-protobuf object tree — the reference paid this cost through
    protobuf's C++ runtime, SURVEY.md §2.2 row 3). Output parses
    identically to ``encode_rollout(...).SerializeToString()``; falls back
    to that when the library is unavailable (or a tensor exceeds 8 dims).

    ``wire_dtype="bfloat16"`` (TransportConfig.rollout_wire_dtype) narrows
    the experience leaves per :func:`rollout_cast_plan` before encoding —
    roughly half the wire bytes per chunk — and ships the ``__wire_cast__``
    marker entry naming exactly what was narrowed. The narrowed arrays ride
    the same ``_SPEC_CACHE`` template path (their dtypes are part of the
    cache key, so f32 and bf16 encodes of the same layout never share a
    template). A rollout with more leaves than the native entry table
    (``_MAX_TENSORS``) raises ``ValueError`` naming the count — encoding
    it would produce payloads the native parser can never decode.
    """
    if native:
        from dotaclient_tpu.native.build import (
            EncodeTensor,
            RolloutHeader,
            load_library,
        )

        lib = load_library()
        if lib is not None and hasattr(lib, "dota_encode_rollout"):
            if _ENC_DTYPE.itemsize != ctypes.sizeof(EncodeTensor):
                # load-bearing ABI check (a bare assert would vanish under
                # python -O and let the C writer read garbage offsets)
                raise ValueError(
                    f"EncodeTensor ABI mismatch: numpy spec row is "
                    f"{_ENC_DTYPE.itemsize} bytes, C struct is "
                    f"{ctypes.sizeof(EncodeTensor)}"
                )
            flat = flatten_tree(arrays)
            flat, marker = _narrow_rollout_flat(flat, wire_dtype, int_bounds)
            n_entries = (
                len(flat)
                + (1 if marker is not None else 0)
                + (1 if trace is not None else 0)
            )
            if n_entries > _MAX_TENSORS:
                _raise_too_many_tensors(n_entries, "encode")
            if all(a.ndim <= 8 for a in flat.values()):
                names = list(flat)
                arrs = [np.ascontiguousarray(a) for a in flat.values()]
                dnames = [_dtype_name(a.dtype) for a in arrs]
                if marker is not None:
                    # the marker rides as one more entry: uint8 blob bytes,
                    # dtype string "marker" (decode intercepts by NAME, so
                    # the string only needs to match the proto path's)
                    names.append(_WIRE_CAST_MARKER)
                    arrs.append(np.frombuffer(marker, np.uint8))
                    dnames.append("marker")
                if trace is not None:
                    # trace blobs are padded to tracing.TRACE_WIRE_LEN, so
                    # the _SPEC_CACHE layout key below stays ONE key per
                    # rollout structure, traced or not
                    names.append(_TRACE_MARKER)
                    arrs.append(np.frombuffer(trace, np.uint8))
                    dnames.append("marker")
                n = len(names)
                # Rollout structure is fixed across an actor's lifetime, so
                # everything but the data pointers — the EncodeTensor table,
                # the names/dtypes blob, the size bound — is cached per
                # (names, dtypes, shapes) key; the steady-state cost per call
                # is one column write plus the C pass. Narrowed layouts get
                # their own key (the dtypes differ), so toggling
                # rollout_wire_dtype can never serve a stale template.
                key = tuple(
                    (name, dname, a.shape)
                    for name, dname, a in zip(names, dnames, arrs)
                )
                cached = _SPEC_CACHE.get(key)
                if cached is None:
                    specs = np.zeros(n, _ENC_DTYPE)
                    pieces = []
                    pos = 0
                    cap = 64
                    for i, (name, dtype_name, shape) in enumerate(key):
                        nb, db = name.encode(), dtype_name.encode()
                        pieces += [nb, db]
                        specs["name_off"][i] = pos
                        specs["name_len"][i] = len(nb)
                        specs["dtype_off"][i] = pos + len(nb)
                        specs["dtype_len"][i] = len(db)
                        pos += len(nb) + len(db)
                        specs["data_len"][i] = arrs[i].nbytes
                        specs["shape"][i, : len(shape)] = shape
                        specs["ndim"][i] = len(shape)
                        cap += arrs[i].nbytes + len(nb) + len(db) + 128
                    cached = (specs, b"".join(pieces), cap)
                    _SPEC_CACHE[key] = cached
                template, strings, cap = cached
                specs = template.copy()  # concurrent encoders don't share
                specs["data_ptr"] = [
                    a.__array_interface__["data"][0] for a in arrs
                ]
                hdr = RolloutHeader(
                    model_version, env_id, rollout_id, length, total_reward
                )
                spec_ptr = specs.ctypes.data_as(ctypes.POINTER(EncodeTensor))
                out = np.empty(cap, np.uint8)
                written = lib.dota_encode_rollout(
                    ctypes.byref(hdr), strings, spec_ptr, n,
                    out.ctypes.data, cap,
                )
                if written > cap:  # estimate too small: size back, retry once
                    out = np.empty(written, np.uint8)
                    written = lib.dota_encode_rollout(
                        ctypes.byref(hdr), strings, spec_ptr, n,
                        out.ctypes.data, written,
                    )
                del arrs  # pinned the numpy buffers across the C calls
                if written >= 0:
                    # bytes-like, not bytes: a second whole-payload memcpy
                    # (`tobytes`) would halve the single-copy win; sockets,
                    # ParseFromString, and len() all take the view directly
                    return out[:written].data
    return encode_rollout(
        arrays, model_version, env_id, rollout_id, length, total_reward,
        wire_dtype=wire_dtype, int_bounds=int_bounds, trace=trace,
    ).SerializeToString()


# In-band wire-narrowing marker (the proto schemas predate wire_dtype and
# protoc is unavailable in this image to extend them): a pseudo-entry in
# the params/arrays map recording exactly what the encoder narrowed.
# Weights fanout: ``data`` lists the leaf names cast f32→bf16,
# newline-joined — decode upcasts ONLY those, so a natively-bf16 param
# (model.param_dtype="bfloat16") is never silently widened. Rollout
# payloads (ISSUE 7): ``data`` lists ``name=orig_dtype`` lines (mixed
# bf16/int8/int16 casts need the original dtype to restore exactly). The
# "/"-free dunder name cannot collide with real leaves (flax param paths
# always nest at least one module level; rollout leaves all nest under
# obs/actions/carry0 or are known scalar-track names).
_WIRE_CAST_MARKER = "__wire_cast__"

# Pipeline-tracing marker (ISSUE 12): the same in-band pseudo-entry
# discipline carries a compact trace record (utils/tracing.py blob —
# origin pid/actor, trace id, weights version at collect, hop
# timestamps) on sampled rollout chunks, every weights-publish frame a
# tracing learner emits, and serve request/reply frames. Decode
# intercepts it by name into ``meta["trace_blob"]`` (rollouts) or via
# :func:`weights_trace` (weights) — it is never a data leaf.
_TRACE_MARKER = "__trace__"


def weights_trace(msg: pb.ModelWeights) -> "bytes | None":
    """The raw trace blob a tracing learner attached to this weights
    frame (None when absent). ``in`` before indexing: protobuf map
    ``__getitem__`` auto-inserts."""
    if _TRACE_MARKER in msg.params:
        return msg.params[_TRACE_MARKER].data
    return None


def encode_weights(
    params: Any, version: int, wire_dtype: str = "float32",
    trace: "bytes | None" = None,
) -> pb.ModelWeights:
    """Serialize a param pytree for the weights fanout.

    ``wire_dtype="bfloat16"`` casts float32 leaves to bf16 at encode —
    half the fanout bytes per publish (TransportConfig.wire_dtype); the
    decode side upcasts exactly those leaves on apply (recorded in an
    in-band marker entry). Non-f32 leaves (int counters, natively-bf16
    params) pass through unchanged in both directions.

    Device-resident params are fetched with ONE batched ``jax.device_get``
    over the whole tree — one host↔device sync per publish instead of one
    per leaf (ISSUE 5); host arrays pass through untouched. The async
    snapshot engine already hands this function host arrays, so its calls
    never sync at all.
    """
    if wire_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown wire_dtype {wire_dtype!r}")
    cast = None
    if wire_dtype == "bfloat16":
        cast = _BFLOAT16
    msg = pb.ModelWeights(version=version)
    cast_names = []
    flat = flatten_tree(params)
    if any(not isinstance(a, np.ndarray) for a in flat.values()):
        import jax  # deferred: the codec itself stays importable jax-free

        flat = jax.device_get(flat)  # host-sync-ok: ONE batched fetch per publish
    for name, arr in flat.items():
        a = np.asarray(arr)
        if cast is not None and a.dtype == np.float32:
            a = a.astype(cast)
            cast_names.append(name)
        msg.params[name].CopyFrom(tensor_to_proto(a))
    if cast_names:
        msg.params[_WIRE_CAST_MARKER].CopyFrom(
            pb.TensorProto(dtype="marker", data="\n".join(cast_names).encode())
        )
    if trace is not None:
        # publish-side trace record (ISSUE 12): origin pid + publish hop,
        # so the actor's apply event can attribute fanout latency without
        # any clock handshake beyond the shared epoch alignment
        msg.params[_TRACE_MARKER].CopyFrom(
            pb.TensorProto(dtype="marker", data=trace)
        )
    return msg


def decode_weights(msg: pb.ModelWeights, upcast: bool = True) -> Tuple[int, Any]:
    """Decode a weights fanout message → ``(version, param pytree)``.

    With ``upcast`` (the apply-side default) the leaves the encoder
    narrowed to bf16 come back as float32 — the lossless inverse of the
    ``wire_dtype="bfloat16"`` cast (every bf16 value is exactly
    representable in f32). Leaves that were bf16 BEFORE encode carry no
    marker and keep their dtype. ``upcast=False`` returns the raw wire
    dtypes (tests, inspection)."""
    cast_names = frozenset()
    # `in` before indexing: protobuf message-map __getitem__ auto-inserts
    if _WIRE_CAST_MARKER in msg.params:
        cast_names = frozenset(
            msg.params[_WIRE_CAST_MARKER].data.decode().split("\n")
        )
    flat = {}
    for name, t in msg.params.items():
        if name in (_WIRE_CAST_MARKER, _TRACE_MARKER):
            continue
        arr = proto_to_tensor(t)
        if (
            upcast
            and name in cast_names
            and arr.dtype == _BFLOAT16
        ):
            arr = arr.astype(np.float32)
        flat[name] = arr
    return msg.version, unflatten_tree(flat)
