"""Serialized KV-page handoff — the disaggregated prefill/decode wire.

ISSUE 12's disaggregation leg: a prefill host runs chunked prefill and
hands the finished KV pages to a decode host, so bursty prefill stops
stealing decode boundaries fleet-wide.  The unit of that transfer is a
:class:`KVHandoff`: one slot's page-table metadata (context tokens,
valid length, geometry) plus the raw page contents the source decoder
gathered (``GPTDecoder.gather_pages``, bucket-padded like every other
page program).  The container is *bytes-serializable* — a JSON header
line followed by the raw page payload with a CRC32 — because a real
deployment ships it over the wire, and because a corrupted transfer
must RAISE (:class:`HandoffError`) into the router's recompute
fallback, never hang or silently import garbage K/V.

Import path: ``PagePool.import_slot`` maps fresh exclusively-owned
pages (refcount 1 each — page-identity semantics: shared/COW'd source
pages arrive as plain content, the destination owns its copies), then
``GPTDecoder.adopt_pages`` scatters the contents and sets the slot
length in ONE donated dispatch, and ``ServeEngine.adopt`` resumes
decoding from the last uncommitted token.  Under greedy decoding the
handed-off continuation is token-identical to decoding in place — and
to the recompute fallback — which is what makes a lost transfer
recoverable.

No jax import here: a handoff is plain host data (numpy + json), so
the container can be parsed by a process that never touches a device.
"""
from __future__ import annotations

import dataclasses
import json
import zlib
from typing import List, Optional, Tuple

import numpy as np

__all__ = ["CHUNK_SCHEMA", "HANDOFF_SCHEMA", "HandoffError", "KVHandoff",
           "KVHandoffChunk"]

HANDOFF_SCHEMA = "apex_tpu.kv_handoff.v1"
CHUNK_SCHEMA = "apex_tpu.kv_handoff_chunk.v1"


class HandoffError(RuntimeError):
    """A handoff container failed validation (truncated bytes, CRC
    mismatch, schema/geometry disagreement).  Raised EAGERLY at parse
    or import time so the caller can fall back to recompute-style
    preemption instead of importing corrupt K/V."""


@dataclasses.dataclass
class KVHandoff:
    """One slot's KV pages in transit between hosts.

    ``tokens`` is the context the pages encode (positions ``[0,
    length)`` — the prompt, plus any generated tokens whose K/V was
    already written); ``seed_tokens`` are the sampled-but-uncommitted
    tokens riding along (at minimum the first token the prefill host
    sampled from its final chunk logits — its K/V is written by the
    destination's next decode window, exactly as it would have been at
    the source).  ``k``/``v`` are ``(n_pages, layers, heads, page_len,
    head_dim)`` page contents in logical order; int8 pools carry their
    per-token fp32 scale columns in ``k_scale``/``v_scale``.
    """

    tokens: List[int]
    seed_tokens: List[int]
    length: int
    page_len: int
    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None
    # fleet correlation id (ISSUE 15): minted by the router at submit,
    # stamped into the wire header so BOTH hosts' telemetry carries the
    # same id and ``trace_report --merge`` stitches the causal flow
    corr: Optional[str] = None

    @property
    def n_pages(self) -> int:
        return self.k.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def payload_bytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n

    def __post_init__(self):
        if self.k.shape != self.v.shape:
            raise HandoffError(
                f"k/v shape mismatch: {self.k.shape} vs {self.v.shape}"
            )
        if self.length < 1 or self.length > self.n_pages * self.page_len:
            raise HandoffError(
                f"length {self.length} outside the {self.n_pages} "
                f"page(s) of {self.page_len} the handoff carries"
            )
        if not self.seed_tokens:
            raise HandoffError(
                "a handoff needs at least one uncommitted seed token "
                "(the sampled continuation the destination resumes from)"
            )

    # -- serialization (the wire format the corruption test attacks) ----

    def to_bytes(self) -> bytes:
        """JSON header line + raw page payload.  The header pins the
        payload's CRC32 and segment layout; :meth:`from_bytes` refuses
        anything that does not round-trip exactly."""
        segs = [self.k, self.v]
        if self.k_scale is not None:
            segs += [self.k_scale, self.v_scale]
        payload = b"".join(np.ascontiguousarray(s).tobytes()
                           for s in segs)
        header = {
            "schema": HANDOFF_SCHEMA,
            "tokens": [int(t) for t in self.tokens],
            "seed_tokens": [int(t) for t in self.seed_tokens],
            "length": int(self.length),
            "page_len": int(self.page_len),
            "shape": list(self.k.shape),
            "dtype": str(self.k.dtype),
            "quantized": self.k_scale is not None,
            "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
        }
        if self.corr is not None:
            header["corr"] = str(self.corr)
        return json.dumps(header, sort_keys=True).encode() + b"\n" + payload

    @classmethod
    def from_bytes(cls, blob: bytes) -> "KVHandoff":
        """Parse + validate; any damage raises :class:`HandoffError`."""
        nl = blob.find(b"\n")
        if nl < 0:
            raise HandoffError("truncated handoff: no header terminator")
        try:
            header = json.loads(blob[:nl].decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise HandoffError(f"unparseable handoff header: {e}") from e
        if header.get("schema") != HANDOFF_SCHEMA:
            raise HandoffError(
                f"unknown handoff schema {header.get('schema')!r}"
            )
        payload = blob[nl + 1:]
        if (zlib.crc32(payload) & 0xFFFFFFFF) != header.get("crc32"):
            raise HandoffError(
                "handoff payload CRC mismatch — page contents were "
                "corrupted in transit"
            )
        try:
            shape = tuple(int(s) for s in header["shape"])
            dtype = np.dtype(header["dtype"])
            per = int(np.prod(shape)) * dtype.itemsize
            k = np.frombuffer(payload[:per], dtype).reshape(shape)
            v = np.frombuffer(payload[per:2 * per], dtype).reshape(shape)
            k_scale = v_scale = None
            if header.get("quantized"):
                sshape = shape[:4]
                sper = int(np.prod(sshape)) * 4
                off = 2 * per
                k_scale = np.frombuffer(
                    payload[off:off + sper], np.float32
                ).reshape(sshape)
                v_scale = np.frombuffer(
                    payload[off + sper:off + 2 * sper], np.float32
                ).reshape(sshape)
            return cls(
                tokens=[int(t) for t in header["tokens"]],
                seed_tokens=[int(t) for t in header["seed_tokens"]],
                length=int(header["length"]),
                page_len=int(header["page_len"]),
                k=k, v=v, k_scale=k_scale, v_scale=v_scale,
                corr=header.get("corr"),
            )
        except HandoffError:
            raise
        except Exception as e:  # short payload, bad shape, ...
            raise HandoffError(f"malformed handoff payload: {e}") from e

    def compatible_with(self, cache) -> Tuple[bool, str]:
        return _geometry_check(self, cache)


def _geometry_check(container, cache) -> Tuple[bool, str]:
    """Shared geometry check for :class:`KVHandoff` /
    :class:`KVHandoffChunk` against a destination ``PagedKVCache``."""
    want = (cache.layers, cache.heads, cache.page_len, cache.head_dim)
    have = container.k.shape[1:]
    if have != want:
        return False, f"page geometry {have} != cache {want}"
    if container.page_len != cache.page_len:
        return False, (f"page_len {container.page_len} != "
                       f"{cache.page_len}")
    if str(container.k.dtype) != str(np.dtype(cache.k.dtype)):
        return False, (f"dtype {container.k.dtype} != "
                       f"{np.dtype(cache.k.dtype)}")
    if container.quantized != (cache.k_scale is not None):
        return False, "quantization mode mismatch"
    return True, ""


@dataclasses.dataclass
class KVHandoffChunk:
    """One page-aligned SLICE of a slot's KV in transit — the streaming
    handoff's wire unit (ISSUE 17).

    A stream is a sequence of chunks with consecutive ``seq`` numbers
    carrying pages ``[page_offset, page_offset + n_pages)`` in logical
    order; the FINAL chunk additionally carries the monolithic
    handoff's resume metadata (``tokens``/``seed_tokens``/``length``)
    and may carry zero pages when every page already shipped.  Chunks
    share :class:`KVHandoff`'s framing (JSON header + CRC'd raw
    payload) so a corrupted or truncated chunk raises
    :class:`HandoffError` into the router's recompute fallback instead
    of importing garbage mid-stream.
    """

    seq: int
    page_offset: int
    page_len: int
    k: np.ndarray
    v: np.ndarray
    k_scale: Optional[np.ndarray] = None
    v_scale: Optional[np.ndarray] = None
    # final-chunk resume metadata (None on interior chunks)
    tokens: Optional[List[int]] = None
    seed_tokens: Optional[List[int]] = None
    length: Optional[int] = None
    corr: Optional[str] = None

    @property
    def n_pages(self) -> int:
        return self.k.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def final(self) -> bool:
        return self.length is not None

    @property
    def payload_bytes(self) -> int:
        n = self.k.nbytes + self.v.nbytes
        if self.k_scale is not None:
            n += self.k_scale.nbytes + self.v_scale.nbytes
        return n

    def __post_init__(self):
        if self.k.shape != self.v.shape:
            raise HandoffError(
                f"k/v shape mismatch: {self.k.shape} vs {self.v.shape}"
            )
        if self.seq < 0 or self.page_offset < 0:
            raise HandoffError(
                f"negative chunk coordinates (seq {self.seq}, "
                f"page_offset {self.page_offset})"
            )
        if not self.final and self.n_pages < 1:
            raise HandoffError("interior chunk carries no pages")
        if self.final:
            if not self.seed_tokens:
                raise HandoffError(
                    "final chunk needs at least one uncommitted seed "
                    "token (the sampled continuation)"
                )
            total = (self.page_offset + self.n_pages) * self.page_len
            if self.length is None or self.length < 1 \
                    or self.length > total:
                raise HandoffError(
                    f"final-chunk length {self.length} outside the "
                    f"{total} position(s) the stream covers"
                )

    def to_bytes(self) -> bytes:
        """Same framing as :meth:`KVHandoff.to_bytes` — a JSON header
        pinning the payload CRC32, then the raw page contents."""
        segs = [self.k, self.v]
        if self.k_scale is not None:
            segs += [self.k_scale, self.v_scale]
        payload = b"".join(np.ascontiguousarray(s).tobytes()
                           for s in segs)
        header = {
            "schema": CHUNK_SCHEMA,
            "seq": int(self.seq),
            "page_offset": int(self.page_offset),
            "page_len": int(self.page_len),
            "shape": list(self.k.shape),
            "dtype": str(self.k.dtype),
            "quantized": self.k_scale is not None,
            "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
        }
        if self.final:
            header["tokens"] = [int(t) for t in self.tokens]
            header["seed_tokens"] = [int(t) for t in self.seed_tokens]
            header["length"] = int(self.length)
        if self.corr is not None:
            header["corr"] = str(self.corr)
        return json.dumps(header, sort_keys=True).encode() + b"\n" + payload

    @classmethod
    def from_bytes(cls, blob: bytes) -> "KVHandoffChunk":
        """Parse + validate; any damage raises :class:`HandoffError`."""
        nl = blob.find(b"\n")
        if nl < 0:
            raise HandoffError("truncated chunk: no header terminator")
        try:
            header = json.loads(blob[:nl].decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise HandoffError(f"unparseable chunk header: {e}") from e
        if header.get("schema") != CHUNK_SCHEMA:
            raise HandoffError(
                f"unknown chunk schema {header.get('schema')!r}"
            )
        payload = blob[nl + 1:]
        if (zlib.crc32(payload) & 0xFFFFFFFF) != header.get("crc32"):
            raise HandoffError(
                "chunk payload CRC mismatch — page contents were "
                "corrupted in transit"
            )
        try:
            shape = tuple(int(s) for s in header["shape"])
            dtype = np.dtype(header["dtype"])
            per = int(np.prod(shape)) * dtype.itemsize
            k = np.frombuffer(payload[:per], dtype).reshape(shape)
            v = np.frombuffer(payload[per:2 * per], dtype).reshape(shape)
            k_scale = v_scale = None
            if header.get("quantized"):
                sshape = shape[:4]
                sper = int(np.prod(sshape)) * 4
                off = 2 * per
                k_scale = np.frombuffer(
                    payload[off:off + sper], np.float32
                ).reshape(sshape)
                v_scale = np.frombuffer(
                    payload[off + sper:off + 2 * sper], np.float32
                ).reshape(sshape)
            tokens = header.get("tokens")
            seeds = header.get("seed_tokens")
            return cls(
                seq=int(header["seq"]),
                page_offset=int(header["page_offset"]),
                page_len=int(header["page_len"]),
                k=k, v=v, k_scale=k_scale, v_scale=v_scale,
                tokens=None if tokens is None
                else [int(t) for t in tokens],
                seed_tokens=None if seeds is None
                else [int(t) for t in seeds],
                length=(None if header.get("length") is None
                        else int(header["length"])),
                corr=header.get("corr"),
            )
        except HandoffError:
            raise
        except Exception as e:  # short payload, bad shape, ...
            raise HandoffError(f"malformed chunk payload: {e}") from e

    def compatible_with(self, cache) -> Tuple[bool, str]:
        return _geometry_check(self, cache)
