"""End-to-end streaming parse engine (paper §4.4).

The paper overlaps three pipeline stages per partition — transfer in, parse,
return — on the PCIe bus's full-duplex channels with a device-side double
buffer and a *carry-over*: the trailing incomplete record of partition *i*
is prepended to partition *i+1*.

JAX mapping (DESIGN.md §3): XLA's async dispatch is the stream engine, and
:class:`StreamSession` keeps the whole carry path on the device so nothing
serialises it.  The per-partition step is ONE donated jitted function —
``backend.prepend_carry`` (splice the device-resident carry in front of the
fresh bytes) → ``stages.execute_plan`` (the same :class:`stages.ParsePlan`
executor every driver runs) → ``backend.extract_carry`` (cut the new tail
after ``last_record_end``) — whose carry outputs feed the next dispatch
*directly*, as device arrays.  No ``int(result.last_record_end)``, no host
``bytes`` slicing: the host thread only cuts source bytes into fixed-size
takes and reads results **one partition behind** the dispatch (the paper's
Fig. 7 timeline: transfer-in of partition *i+1* and the read-back of
partition *i−1* both overlap the parse of partition *i*).  Because every
partition reuses one compiled executable (static capacity), there is no
recompilation in the steady state.

The carry boundary comes from parse *metadata*, not from a host ``rfind``:
a newline inside a quoted field must not be mistaken for a record boundary,
which is exactly the context problem the paper solves.

**Multi-stream batching**: ``StreamSession(n_streams=S)`` ``vmap``s the
step over a leading stream axis — per-stream carry buffers, per-stream
flush flags — so S independent sources (concurrent tenants) parse in one
dispatch per round, bit-identical to S sequential single-stream sessions
(pinned by ``tests/test_streaming.py``).

**Lane sharding**: with ``mesh=`` the stream axis is additionally sharded
over a mesh axis (``shard_map`` around the vmapped step): each device owns
``S/D`` lanes, their carry buffers stay device-resident round over round
(no carry leaf ever crosses devices, no collectives in the step), and one
dispatch still drives the whole fleet — bit-identical to the single-device
batched engine (pinned by ``tests/test_distributed.py``).

:class:`StreamingParser` is the legacy iterator API, now a thin wrapper
over a single-stream session (``engine="device"``); ``engine="host"``
keeps the original host-carry loop — one blocking sync per partition —
as the bit-identity oracle the device engine is tested against.

Both engines compose :class:`Parser`'s plan, so they inherit the
backend-owned materialization path untouched: with ``backend="pallas"``
every partition runs the radix partition kernel and the fused
gather+convert typeconv kernels with zero changes here.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterable, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax import shard_map

from repro.core import spans
from repro.core import stages as stages_mod
from repro.core.dfa import PAD_BYTE
from repro.core.parser import ParseResult, Parser

#: The engine's ONLY device→host read goes through this indirection — an
#: *explicit* transfer, so a session keeps running under
#: ``jax.transfer_guard_device_to_host("disallow")`` (which traps implicit
#: ``int(...)``/``.item()``/``np.asarray`` syncs).  Tests monkeypatch it to
#: count fetches and assert they trail dispatches by one partition.
_device_get = jax.device_get

#: Ids of ``parse_streams`` calls, process-wide: every host span of a call
#: carries ``call=<id>`` (``repro.core.spans``).
_CALL_IDS = itertools.count(1)


class StreamOverflow(ValueError):
    """Typed per-stream overflow record.

    A record longer than the session capacity cannot be parsed: the carry
    splice wraps and the lane's buffer contents are garbage.  In a batched
    session this is a *per-lane* fault, not a session fault —
    :meth:`StreamSession.parse_streams` yields ``(stream, StreamOverflow,
    0)`` on the failed lane's channel and keeps parsing every other lane
    (fault isolation for multi-tenant serving).  The single-stream
    :class:`StreamingParser` re-raises it, so legacy callers still see a
    ``ValueError`` with the historical message.
    """

    def __init__(self, stream: int, n_bytes: int, capacity: int,
                 n_streams: int = 1):
        self.stream = int(stream)
        self.n_bytes = int(n_bytes)
        self.capacity = int(capacity)
        super().__init__(
            f"record longer than capacity ({n_bytes} > {capacity}); "
            "increase max_carry_bytes"
            + (f" [stream {stream}]" if n_streams > 1 else "")
        )


@dataclasses.dataclass
class StreamStats:
    """Per-stream accounting.  Exact definitions:

    ``partitions``
        Parsed partitions yielded to the caller (suppressed no-op rounds of
        a batched session are not counted).
    ``bytes_in``
        Raw *source* bytes consumed, each counted exactly once — the
        denominator for honest end-to-end throughput.  Carry bytes that
        re-enter the next partition are **not** re-counted here.
    ``bytes_reparsed``
        Carry-over bytes parsed a second (or third, …) time because their
        record straddled a partition boundary.  Device work per stream is
        proportional to ``bytes_in + bytes_reparsed``; a high ratio means
        the partition size is too small for the record length.
    ``records``
        Complete records across all yielded partitions.
    ``max_carry``
        Largest carry that *survived* a partition (after the
        final-partition stale-carry drop), i.e. the minimum
        ``max_carry_bytes`` this stream would have needed.
    ``flush_delims``
        Synthetic flush delimiters appended on-device (one per flush round
        whose stream did not end on a record delimiter).  These bytes are
        *parsed* but are not source bytes, so they are counted here and
        **not** in ``bytes_in``: total device-parsed bytes for a stream are
        exactly ``bytes_in + bytes_reparsed + flush_delims``, while GB/s
        denominators should keep using ``bytes_in`` (each source byte once).
    ``failed``
        The stream hit a :class:`StreamOverflow` and its lane was retired
        for the rest of the call; ``bytes_in``/``bytes_reparsed`` include
        the overflowing round (the work was dispatched), but
        ``partitions``/``records`` do not (nothing usable came back).
    """

    partitions: int = 0
    bytes_in: int = 0
    bytes_reparsed: int = 0
    records: int = 0
    max_carry: int = 0
    flush_delims: int = 0
    failed: bool = False


class _StepAux(NamedTuple):
    """Tiny per-partition scalars the host reads one round behind.

    Deliberately does NOT alias the donated carry outputs: the next round's
    dispatch donates ``(carry_buf, carry_len)``, which would invalidate any
    aux leaf sharing their buffers before the one-behind fetch reads it
    (``last_record_end`` lets the host re-derive the carry length from
    values it already knows instead).
    """

    n_records: jax.Array        # () / (S,) int32 — complete records
    last_record_end: jax.Array  # () / (S,) int32 — §4.4 carry boundary
    overflow: jax.Array         # () / (S,) bool  — partition no longer fits


class _Feed:
    """Host-side cursor cutting one ``Iterable[bytes]`` into partition takes.

    Every stream ends with exactly one ``flush=True`` take (possibly empty:
    the source exhausted at a partition boundary); after that ``next_take``
    returns ``None`` and the stream's lane goes inert.
    """

    def __init__(self, source: Iterable[bytes], partition_bytes: int,
                 call: int):
        self._it = iter(source)
        self._call = call
        self._buf = b""
        self._pb = partition_bytes
        self.exhausted = False
        self.flushed = False
        #: Last non-PAD byte produced so far — the host mirror of the
        #: device's flush-delimiter judgement (append one iff the stream's
        #: last payload byte is not already a record delimiter).
        self.last_payload: Optional[int] = None

    def next_take(self) -> Optional[Tuple[bytes, bool]]:
        if self.flushed:
            return None
        while not self.exhausted and len(self._buf) < self._pb:
            try:
                with spans.span("stream.pull", call=self._call):
                    piece = next(self._it)
            except StopIteration:
                self.exhausted = True
            else:
                self._buf += piece
        take, self._buf = self._buf[: self._pb], self._buf[self._pb:]
        flush = self.exhausted and not self._buf
        if flush:
            self.flushed = True
        payload = take.rstrip(bytes([PAD_BYTE]))
        if payload:
            self.last_payload = payload[-1]
        return take, flush

    def kill(self) -> None:
        """Retire the lane (fault isolation): subsequent ``next_take``
        calls return ``None`` and the lane goes inert."""
        self.flushed = True


class StreamSession:
    """Device-resident streaming engine with dispatch-ahead and multi-stream
    batching (see module docstring).

    Args:
      parser: a configured :class:`Parser`; its ``max_records`` bounds
        records *per partition per stream*, and its :class:`ParsePlan` is
        the one the session step executes.
      partition_bytes: raw bytes consumed from each source per partition.
      max_carry_bytes: capacity reserved for the carry-over (longest record
        any stream may contain — the paper's carry-over allocation).
      n_streams: number of independent sources batched per dispatch
        (leading ``vmap`` axis of the step; per-stream carry state).
      mesh: optional device mesh — lanes are sharded over ``mesh_axis``
        (``n_streams`` must divide by its size), each device owning a
        disjoint lane set whose carry buffers stay resident on that
        device across rounds (the carry never crosses devices; the step
        compiles with zero collectives).  One dispatch per round drives
        every device; results are bit-identical to the same session
        without a mesh.
      mesh_axis: the mesh axis name lanes shard over.

    ``stats`` is one :class:`StreamStats` per stream, accumulated across
    ``parse_streams`` calls (carry state resets per call); ``call_stats``
    is the same accounting reset at the start of every ``parse_streams``
    call — what a serving layer reports per tenant per batch.

    A session drives ONE ``parse_streams`` generator at a time: its carry
    buffers are donated between rounds and a dispatched round may still be
    in flight when the generator is abandoned, so re-entry is guarded by a
    state machine (``idle`` → ``active`` → ``idle`` | ``dirty``).  A
    generator that exits abnormally (caller ``break``/``close`` or an
    exception) leaves the session ``dirty``; call :meth:`reset` to settle
    the in-flight round and return to ``idle``.
    """

    def __init__(self, parser: Parser, partition_bytes: int,
                 max_carry_bytes: Optional[int] = None, n_streams: int = 1,
                 mesh: Optional[Mesh] = None, mesh_axis: str = "streams"):
        self.parser = parser
        self.partition_bytes = int(partition_bytes)
        self.max_carry_bytes = int(max_carry_bytes or partition_bytes)
        k = parser.cfg.chunk_size
        cap = self.partition_bytes + self.max_carry_bytes + 1
        self.capacity = ((cap + k - 1) // k) * k
        if self.partition_bytes < 1:
            raise ValueError(
                f"partition_bytes must be >= 1, got {partition_bytes}")
        self.n_streams = int(n_streams)
        if self.n_streams < 1:
            raise ValueError(f"n_streams must be >= 1, got {n_streams}")
        # Lane sharding (mesh mode): the stream axis is sharded over a mesh
        # axis — each device owns a disjoint lane set and its lanes' carry
        # buffers live on that device for the whole session (the step's
        # in/out specs keep every leaf P(axis), so no carry leaf ever
        # crosses devices and the step body compiles with ZERO collectives —
        # pinned by tests/test_distributed.py).  Bit-identical to the
        # single-device batched engine: the step body is the same vmapped
        # function, merely partitioned along the lane axis.
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        if mesh is not None:
            if mesh_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no axis {mesh_axis!r}: {mesh.axis_names}")
            d = mesh.shape[mesh_axis]
            if self.n_streams % d:
                raise ValueError(
                    f"n_streams={self.n_streams} not divisible by mesh axis "
                    f"{mesh_axis!r} size {d}")
        #: Mesh mode always uses batched (leading-stream-axis) shapes, even
        #: for S == 1, so the shard_map specs are uniform.
        self._batched = self.n_streams > 1 or mesh is not None
        self._lane_sharding = (
            None if mesh is None
            else NamedSharding(mesh, PartitionSpec(mesh_axis)))
        # Double-buffered staging: round r+1 is assembled in one buffer
        # while the other may still back round r's in-flight transfer.
        # Stale bytes beyond a take need no re-padding — prepend_carry masks
        # the fresh buffer at fresh_len, so only [0, len(take)) is ever read.
        # Staging is PARTITION-sized, not capacity-sized: only fresh source
        # bytes cross the bus each round; the jitted step zero-extends to
        # capacity on-device (the carry tail never transfers).
        S = self.n_streams
        self._staging = [np.full((S, self.partition_bytes), PAD_BYTE, np.uint8)
                         for _ in range(2)]
        self._staging_idx = 0
        self.stats: Tuple[StreamStats, ...] = tuple(StreamStats() for _ in range(S))
        self.call_stats: Tuple[StreamStats, ...] = tuple(
            StreamStats() for _ in range(S))
        self._state = "idle"        # idle | active | dirty
        self._failed = [False] * S  # per-lane fault flags, reset per call
        self._inflight = None       # last dispatched round's device outputs
        self._step = self._build_step()

    # -- the donated per-partition device step -------------------------------
    def _build_step(self):
        parser = self.parser
        cfg, backend, plan = parser.cfg, parser.backend, parser.plan
        k = cfg.chunk_size

        capacity = self.capacity

        def step_one(carry_buf, carry_len, fresh, fresh_len, flush):
            # The carry work runs under the same ``stage.*`` scopes as the
            # stages of execute_plan (core/stages.py).
            with jax.named_scope("stage.carry"):
                # The host transfers only the partition-sized fresh bytes;
                # extend to the carry capacity on-device (PAD tail, fused
                # into the splice by XLA — nothing extra crosses the bus).
                pad = capacity - fresh.shape[-1]
                if pad:
                    fresh = jnp.concatenate(
                        [fresh, jnp.full((pad,), PAD_BYTE, jnp.uint8)])
                buf, total, overflow = backend.prepend_carry(
                    carry_buf, carry_len, fresh, fresh_len, flush, cfg
                )
            with jax.named_scope("stage.contexts"):
                chunks = buf.reshape(-1, k)
            # execute_plan dispatches staged vs fused (the whole-pipeline
            # megakernel) per the resolved plan — the carry hooks above/
            # below are path-agnostic, so fuse_pipeline streams for free.
            result = stages_mod.execute_plan(chunks, plan, cfg, backend)
            with jax.named_scope("stage.carry"):
                new_buf, new_len = backend.extract_carry(
                    buf, total, result.last_record_end, flush, cfg
                )
                aux = _StepAux(
                    n_records=result.validation.n_records.astype(jnp.int32),
                    last_record_end=result.last_record_end,
                    overflow=overflow,
                )
            return result, new_buf, new_len, aux

        fn = step_one if not self._batched else jax.vmap(step_one)
        if self.mesh is not None:
            # Lane sharding: every in/out leaf is partitioned on its leading
            # stream axis; each device runs the SAME vmapped step over its
            # own S/D lanes.  check_vma=False: nothing is replicated.
            spec = PartitionSpec(self.mesh_axis)
            fn = shard_map(fn, mesh=self.mesh,
                           in_specs=(spec, spec, spec, spec, spec),
                           out_specs=spec, check_vma=False)
        # Donate the carry buffers: partition i+1's step overwrites partition
        # i's carry in place (no device-side copy growth).  CPU/interpret
        # hosts can't alias donations — skip there to keep runs warning-free.
        donate = (0, 1) if jax.default_backend() != "cpu" else ()
        return jax.jit(fn, donate_argnums=donate)

    def _init_carry(self):
        S = self.n_streams
        shape = (S, self.capacity) if self._batched else (self.capacity,)
        lshape = (S,) if self._batched else ()
        buf = jnp.full(shape, PAD_BYTE, jnp.uint8)
        ln = jnp.zeros(lshape, jnp.int32)
        if self._lane_sharding is not None:
            # Carry locality: buffers start on their owning device and the
            # step's out_specs keep them there — the carry never crosses.
            buf = jax.device_put(buf, self._lane_sharding)
            ln = jax.device_put(ln, self._lane_sharding)
        return buf, ln

    # -- host-side staging ---------------------------------------------------
    def _stage_round(self, feeds: List[_Feed]):
        """Assemble the next round's fresh buffers; ``None`` when every
        stream has dispatched its flush partition."""
        S = self.n_streams
        staging = self._staging[self._staging_idx]
        self._staging_idx ^= 1
        fresh_len = np.zeros(S, np.int32)
        flush = np.zeros(S, bool)
        active = [False] * S
        delims = [False] * S
        for s, feed in enumerate(feeds):
            nt = feed.next_take()
            if nt is None:
                # Inert lane: empty take under flush keeps the (already
                # empty) carry pinned at zero; drained rounds skip it.
                flush[s] = True
                continue
            take, fl = nt
            raw = np.frombuffer(take, np.uint8)
            staging[s, : raw.size] = raw
            fresh_len[s] = raw.size
            flush[s] = fl
            active[s] = True
            if fl:
                # Host mirror of the device's flush-delimiter judgement
                # (for stats only — the device decides independently): a
                # delimiter is appended iff the stream's last payload byte
                # is not already a record delimiter.  The carry is always a
                # contiguous suffix of consumed bytes, so the buffer's last
                # payload byte equals the stream-wide one.
                delims[s] = (
                    feed.last_payload is not None
                    and feed.last_payload != self.parser.cfg.record_delim_byte
                )
        if not any(active):
            return None
        host = staging if self._batched else staging[0]
        fresh = (jax.device_put(host, self._lane_sharding)
                 if self._lane_sharding is not None else jax.device_put(host))
        return fresh, fresh_len, flush, active, delims

    # -- the dispatch-ahead loop ---------------------------------------------
    def parse_streams(
        self, sources: Sequence[Iterable[bytes]]
    ) -> Iterator[Tuple[int, ParseResult, int]]:
        """Drive ``n_streams`` sources to completion, one batched dispatch
        per round, yielding ``(stream, result, n_complete)`` per partition
        in round order.

        Results are read one round behind the dispatch: round *r* is
        yielded only after round *r+1* is in flight, and the only host
        reads are one explicit ``jax.device_get`` of three scalars per
        round (``_StepAux``) — the carry path itself never touches the
        host.  Only records ``[0, n_complete)`` of each result are
        complete; the trailing bytes re-appear in the stream's next
        partition.

        **Fault isolation**: a lane whose record exceeds the capacity
        yields ``(stream, StreamOverflow, 0)`` once, is retired for the
        rest of the call (its remaining source is not consumed, its stats
        are finalized with ``failed=True``), and every other lane parses
        to completion exactly as if the failed lane had never been there.
        No exception crosses lane boundaries.

        **Host spans** (``repro.core.spans``), each with ``call=<id>`` of
        this call: per round ``stream.stage`` (staging; ``bytes``: the fresh
        take bytes) with a ``stream.pull`` child per read of a source,
        ``stream.dispatch`` (the step call), and ``stream.drain`` (the
        one-behind read and its bookkeeping; ``carry_bytes``: the carry
        the round re-parsed, ``records``) with its ``stream.wait`` child
        (the fetch of the round's scalars).  No span is open while the
        caller holds a result.
        """
        if self._state != "idle":
            raise RuntimeError(
                f"StreamSession is {self._state!r}: a previous parse_streams "
                "generator is still open or exited abnormally; exhaust/close "
                "it and call reset() before reuse"
            )
        S = self.n_streams
        sources = list(sources)
        if len(sources) != S:
            raise ValueError(f"expected {S} sources, got {len(sources)}")
        self._state = "active"
        self.call_stats = tuple(StreamStats() for _ in range(S))
        self._failed = [False] * S
        call = next(_CALL_IDS)
        done = False
        try:
            feeds = [_Feed(src, self.partition_bytes, call) for src in sources]
            carry_buf, carry_len = self._init_carry()
            carry_known = [0] * S  # host mirror of carry_len, one round behind
            pending = None
            while True:
                with spans.span("stream.stage", call=call) as sp:
                    staged = self._stage_round(feeds)
                    sp.set(bytes=0 if staged is None else int(staged[1].sum()))
                if staged is None:
                    break
                fresh, fresh_len, flush, active, delims = staged
                # Drop the in-flight record before dispatch: the step donates
                # the previous round's carry outputs, so they must not be
                # retained (reset() would try to block on dead buffers).
                self._inflight = None
                with spans.span("stream.dispatch", call=call):
                    result, carry_buf, carry_len, aux = self._step(
                        carry_buf, carry_len, fresh,
                        jnp.asarray(fresh_len if self._batched else fresh_len[0]),
                        jnp.asarray(flush if self._batched else flush[0]),
                    )
                self._inflight = (result, carry_buf, carry_len, aux)
                if pending is not None:
                    yield from self._drain(pending, carry_known, feeds, call)
                pending = (result, aux, fresh_len, flush, active, delims)
            if pending is not None:
                yield from self._drain(pending, carry_known, feeds, call)
            done = True
        finally:
            if done:
                self._state = "idle"
                self._inflight = None
            else:
                # Abandoned mid-stream (caller break/close or an exception):
                # a dispatched round may still be in flight against donated
                # carry — refuse silent reuse until reset().
                self._state = "dirty"

    def reset(self) -> None:
        """Settle an abnormally-exited session back to ``idle``.

        Blocks on the last dispatched round (so no computation is still
        writing into the donated carry buffers), drops it, and clears the
        state guard.  Cumulative ``stats`` are preserved; the next
        ``parse_streams`` call re-initialises carry state as always.  A
        session with a still-open generator must have it closed first.
        """
        if self._state == "active":
            raise RuntimeError(
                "cannot reset a StreamSession with an open parse_streams "
                "generator; close it first"
            )
        if self._inflight is not None:
            try:
                jax.block_until_ready(self._inflight)
            except Exception:
                pass  # donated-away buffers: already settled by definition
            self._inflight = None
        self._state = "idle"

    def _drain(self, pending, carry_known: List[int], feeds: List[_Feed],
               call: int) -> List[Tuple[int, object, int]]:
        """Fetch one round's scalars (the one-behind read) and return its
        per-stream results; overflowing lanes get a typed
        :class:`StreamOverflow` and are retired without disturbing the
        rest of the batch.  Returned, not yielded, so that the round's
        ``stream.drain`` span closes before the caller sees a result."""
        result, aux, fresh_len, flush, active, delims = pending
        out = []
        with spans.span("stream.drain", call=call) as sp:
            with spans.span("stream.wait", call=call):
                aux_np = _device_get(aux)
            n_records = np.atleast_1d(aux_np.n_records)
            last_end = np.atleast_1d(aux_np.last_record_end)
            overflow = np.atleast_1d(aux_np.overflow)
            carry_bytes = records = 0
            for s in range(self.n_streams):
                if not active[s] or self._failed[s]:
                    # Inert lane, or a failed lane's already-dispatched round
                    # (dispatch runs one ahead of the drain that detects the
                    # overflow): its buffer contents are garbage — suppress.
                    continue
                take_len, carry_in = int(fresh_len[s]), carry_known[s]
                if take_len == 0 and carry_in == 0:
                    # The optimistic end-of-stream flush round found nothing
                    # to parse (the source ended exactly at a partition
                    # boundary, or was empty): a no-op, not a partition.
                    carry_known[s] = 0
                    continue
                carry_bytes += carry_in
                if bool(overflow[s]):
                    # Per-lane fault: the splice wrapped, this lane's buffer
                    # is garbage.  Retire the lane (its feed stops producing;
                    # the next parse_streams call re-inits carry
                    # device-side) and report on this stream's channel only.
                    err = StreamOverflow(
                        s, carry_in + take_len + (1 if flush[s] else 0),
                        self.capacity, self.n_streams)
                    self._failed[s] = True
                    feeds[s].kill()
                    carry_known[s] = 0
                    for st in (self.stats[s], self.call_stats[s]):
                        st.bytes_in += take_len
                        st.bytes_reparsed += carry_in
                        st.failed = True
                    out.append((s, err, 0))
                    continue
                # Mirror of extract_carry: the carry length re-derived from
                # host-known values + the fetched boundary (the donated
                # device carry_len itself is never read back).
                carry_out = 0 if flush[s] else max(
                    carry_in + take_len - (int(last_end[s]) + 1), 0)
                for st in (self.stats[s], self.call_stats[s]):
                    st.partitions += 1
                    st.bytes_in += take_len
                    st.bytes_reparsed += carry_in
                    st.records += int(n_records[s])
                    st.max_carry = max(st.max_carry, carry_out)
                    if flush[s] and delims[s]:
                        st.flush_delims += 1
                carry_known[s] = carry_out
                records += int(n_records[s])
                out.append((s, self._slice_result(result, s), int(n_records[s])))
            sp.set(carry_bytes=carry_bytes, records=records)
        return out

    def _slice_result(self, result: ParseResult, s: int) -> ParseResult:
        if not self._batched:
            return result
        return jax.tree_util.tree_map(lambda x: x[s], result)


class StreamingParser:
    """Partition-pipelined parser with carry-over record stitching — the
    legacy single-stream iterator API.

    ``engine="device"`` (default) wraps a single-stream
    :class:`StreamSession`: device-resident carry, no per-partition host
    sync, results one partition behind dispatch.  ``engine="host"`` keeps
    the original host-carry loop — Python ``bytes`` stitching and one
    blocking ``int(result.last_record_end)`` per partition — as the oracle
    the device engine is pinned bit-identical to.

    Args:
      parser: a configured single-device :class:`Parser`; its
        ``max_records`` bounds records *per partition*.
      partition_bytes: raw bytes consumed from the source per partition.
      max_carry_bytes: capacity reserved for the carry-over (longest record
        the stream may contain, paper's carry-over allocation).
      engine: ``device`` | ``host``.
    """

    def __init__(self, parser: Parser, partition_bytes: int,
                 max_carry_bytes: Optional[int] = None, engine: str = "device"):
        self.parser = parser
        self.partition_bytes = int(partition_bytes)
        self.max_carry_bytes = int(max_carry_bytes or partition_bytes)
        if self.partition_bytes < 1:
            raise ValueError(
                f"partition_bytes must be >= 1, got {partition_bytes}")
        if engine not in ("device", "host"):
            raise ValueError(f"engine must be 'device' or 'host', got {engine!r}")
        self.engine = engine
        if engine == "device":
            self._session = StreamSession(
                parser, self.partition_bytes, max_carry_bytes=self.max_carry_bytes
            )
            self.capacity = self._session.capacity
            self.stats = self._session.stats[0]
        else:
            k = parser.cfg.chunk_size
            cap = self.partition_bytes + self.max_carry_bytes + 1
            self.capacity = ((cap + k - 1) // k) * k
            self.stats = StreamStats()
            # One preallocated staging buffer reused across partitions (the
            # host engine syncs per partition, so the device is done with it
            # before the next rewrite); only the dirtied tail is re-padded.
            self._staging = np.full(self.capacity, PAD_BYTE, np.uint8)
            self._staged = 0

    def parse_stream(
        self, source: Iterable[bytes]
    ) -> Iterator[Tuple[ParseResult, int]]:
        """Yields ``(result, n_complete_records)`` per partition.

        Only records ``[0, n_complete)`` of each result are complete; the
        trailing bytes re-appear at the front of the next partition.
        """
        if self.engine == "device":
            gen = self._session.parse_streams([source])
            try:
                for _s, result, n in gen:
                    if isinstance(result, StreamOverflow):
                        # Single-stream legacy contract: overflow raises
                        # (it is a ValueError subclass with the historical
                        # message).  Batched callers use StreamSession and
                        # get the per-lane typed-result contract instead.
                        raise result
                    yield result, n
            finally:
                gen.close()
                if self._session._state == "dirty":
                    self._session.reset()
        else:
            yield from self._parse_stream_host(source)

    def reset(self) -> None:
        """Settle the underlying session after an abnormal exit
        (device engine only; the host engine is stateless per call)."""
        if self.engine == "device":
            self._session.reset()

    # -- legacy host-carry engine (the bit-identity oracle) ------------------
    def _buf_to_chunks(self, buf: bytes, final: bool) -> np.ndarray:
        k = self.parser.cfg.chunk_size
        raw = np.frombuffer(buf, np.uint8)
        out = self._staging
        out[raw.size : max(self._staged, raw.size + 1)] = PAD_BYTE
        out[: raw.size] = raw
        self._staged = raw.size
        if final:
            # Flush the unterminated tail record — but judge "unterminated"
            # on the last *payload* byte: a PAD-only tail (trailing 0x00
            # padding in the source) carries no record, and appending a
            # delimiter after it would mint a spurious empty record.
            payload = raw.size
            while payload and raw[payload - 1] == PAD_BYTE:
                payload -= 1
            if payload and raw[payload - 1] != self.parser.cfg.record_delim_byte:
                if raw.size >= self.capacity:
                    # The carry consumed the slot reserved for the flush
                    # delimiter (a single record filled the whole buffer).
                    self.stats.failed = True
                    raise StreamOverflow(0, raw.size + 1, self.capacity)
                out[raw.size] = self.parser.cfg.record_delim_byte
                self._staged = raw.size + 1
                self.stats.flush_delims += 1
        return out.reshape(-1, k)

    def _parse_stream_host(self, source: Iterable[bytes]):
        carry = b""
        it = iter(source)
        buf = b""
        exhausted = False
        while True:
            # fill the partition
            while not exhausted and len(buf) < self.partition_bytes:
                try:
                    buf += next(it)
                except StopIteration:
                    exhausted = True
            take = buf[: self.partition_bytes]
            buf = buf[self.partition_bytes:]
            if not take and not carry:
                break
            final = exhausted and not buf
            full = carry + take
            if len(full) > self.capacity:
                self.stats.failed = True
                raise StreamOverflow(0, len(full), self.capacity)
            chunks = self._buf_to_chunks(full, final)
            # The host-carry sync: fetching the carry boundary blocks on the
            # partition's parse — the serialisation StreamSession removes.
            result = self.parser.parse_chunks(jnp.asarray(chunks))
            last = int(result.last_record_end)
            n_complete = int(result.validation.n_records)
            if last < 0:
                carry = full  # no complete record in this partition
            else:
                carry = full[last + 1:]
            if final and carry:
                # The stream is exhausted, so leftover carry is stale, not a
                # pending record: either inert PAD/control bytes (a PAD-only
                # tail — nothing left to parse), or an unterminated record
                # that the appended delimiter could not close (malformed
                # input, e.g. an unclosed quote; ``validation`` flags it).
                # Drop it explicitly so stats and any caller inspecting the
                # carry see the stream as fully consumed.
                carry = b""
            self.stats.partitions += 1
            self.stats.bytes_in += len(take)
            self.stats.bytes_reparsed += len(full) - len(take)
            self.stats.records += n_complete
            self.stats.max_carry = max(self.stats.max_carry, len(carry))
            yield result, n_complete
            if final:
                break

    def parse_all(self, source: Iterable[bytes]):
        """Convenience: fully consume the stream, returning concatenated
        per-column host arrays (Arrow layout, like ``Parser.to_arrow``)."""
        schema = self.parser.cfg.schema
        acc = {c.name: [] for c in schema.columns}
        for result, n in self.parse_stream(source):
            arrow = self.parser.to_arrow(result)
            for c in schema.columns:
                acc[c.name].append(_trim(arrow[c.name], n))
        return {name: _concat(parts) for name, parts in acc.items()}


def _trim(arrow_col: dict, n: int) -> dict:
    if "values" in arrow_col:
        return dict(values=arrow_col["values"][:n],
                    validity=arrow_col["validity"], n=n)
    offsets = arrow_col["offsets"][: n + 1]
    return dict(offsets=offsets, data=arrow_col["data"][: offsets[-1] if n else 0],
                validity=arrow_col["validity"], n=n)


def _unpack_bits(packed: np.ndarray, n: int) -> np.ndarray:
    return np.unpackbits(packed, bitorder="little")[:n].astype(bool)


def _concat(parts) -> dict:
    if not parts:
        return {}
    if "values" in parts[0]:
        values = np.concatenate([p["values"][: p["n"]] for p in parts])
        validity = np.concatenate([_unpack_bits(p["validity"], p["n"]) for p in parts])
        return dict(values=values, validity=validity)
    datas, offs, vals = [], [np.zeros(1, np.int64)], []
    base = 0
    for p in parts:
        n = p["n"]
        o = p["offsets"].astype(np.int64)
        offs.append(o[1 : n + 1] + base)
        datas.append(p["data"][: o[n]])
        vals.append(_unpack_bits(p["validity"], n))
        base += int(o[n])
    return dict(
        offsets=np.concatenate(offs),
        data=np.concatenate(datas) if datas else np.zeros(0, np.uint8),
        validity=np.concatenate(vals),
    )


def iter_file(path: str, read_bytes: int = 1 << 20) -> Iterator[bytes]:
    """Simple file source for ``parse_stream``."""
    with open(path, "rb") as f:
        while True:
            b = f.read(read_bytes)
            if not b:
                return
            yield b
