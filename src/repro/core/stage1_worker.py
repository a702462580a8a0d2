"""Stage-1 chunk decodes, in the calling thread or in worker processes.

The speculative first pass (block finder plus trial decode, paper §3.4)
is pure Python, so decoder threads queue on the interpreter lock: however
many are in flight, they share one core. ``start_pool`` builds a process
pool that runs the same code with a core per worker; the fetcher hands a
task's byte window to it and blocks on the future, which releases the lock.

``trial_decode`` and ``exact_decode`` take a codec instance and run in the
calling thread; ``in_worker`` runs either of them in a pool worker with the
codec rebuilt from its tag and framing, so only codecs that ``offloadable``
accepts are ever sent. The module imports nothing of JAX: a worker process
starts by importing it.
"""

from __future__ import annotations

import functools
import multiprocessing
import os
import time
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, List, Optional

from .codec import CODECS, Codec, resolve_codec
from .deflate import DecodeResult
from .errors import EndOfStream, FormatError


@dataclass
class Trial:
    """One pass of the nominal trial loop over one byte window."""

    result: Optional[DecodeResult]
    #: a trial ran out of bytes before the window reached the file's end
    need_more_data: bool
    #: global bit offsets of the candidates that failed in this pass
    failed: List[int] = field(default_factory=list)
    trials: int = 0
    #: wall time inside the block finder (only when asked to clock it)
    find_s: float = 0.0
    #: bit offsets the finder's dynamic-header scan evaluated
    find_bits: int = 0


def trial_decode(
    codec: Codec,
    buf,
    base_byte: int,
    start_bit: int,
    stop_bit: int,
    *,
    max_out: int,
    at_eof: bool,
    failed: Iterable[int] = (),
    clock: bool = False,
) -> Trial:
    """Find candidate chunk starts in ``[start_bit, stop_bit)`` and decode
    each in marker mode until one survives to the stop condition.

    ``buf`` holds the archive from byte ``base_byte``; every bit offset in
    and out is global. Candidates in ``failed`` are skipped. A trial that
    raises ``FormatError``, or ``EndOfStream`` with the window at the file's
    end, was no chunk start; ``EndOfStream`` short of the end stops the pass
    with ``need_more_data`` so the caller can retry on a wider window.
    """
    base_bits = base_byte * 8
    local_stop = stop_bit - base_bits
    skip = set(failed)
    out = Trial(result=None, need_more_data=False)
    spent = [0.0]
    finder = codec.find_chunk_starts(buf, start_bit - base_bits, local_stop)
    cands = _clocked(spent, finder) if clock else finder
    for cand in cands:
        if cand + base_bits in skip:
            continue
        out.trials += 1
        try:
            res = codec.decode_chunk(buf, cand, local_stop, window=None, max_out=max_out)
        except EndOfStream:
            if not at_eof:
                out.need_more_data = True
                break
        except FormatError:
            # Bad deflate data, or a trial that ran past a final block
            # into bytes that are no gzip header: either way the candidate
            # was no chunk start.
            pass
        else:
            out.result = offset_result(res, base_bits)
            break
        skip.add(cand + base_bits)
        out.failed.append(cand + base_bits)
    out.find_s = spent[0]
    out.find_bits = getattr(finder, "dynamic_bits", 0)
    return out


def exact_decode(
    codec: Codec,
    buf,
    base_byte: int,
    start_bit: int,
    stop_bit: int,
    *,
    window: Optional[bytes],
    max_out: int,
) -> DecodeResult:
    """Decode the chunk that starts exactly at ``start_bit`` (global bits;
    ``buf`` holds the archive from byte ``base_byte``). ``window=None`` is
    marker mode. Errors propagate, ``EndOfStream`` included."""
    base_bits = base_byte * 8
    res = codec.decode_chunk(buf, start_bit - base_bits, stop_bit - base_bits,
                             window=window, max_out=max_out)
    return offset_result(res, base_bits)


# -- worker processes -----------------------------------------------------------


def offloadable(codec: Codec) -> bool:
    """May ``codec``'s first-pass decodes run in a worker? Only speculative
    codecs whose tag rebuilds exactly this class: a worker has the tag and
    framing, not the instance (nor a subclass's overrides)."""
    return codec.supports_speculation and type(codec) is CODECS.get(codec.tag)


@functools.lru_cache(maxsize=None)
def _codec(tag: str, framing: str) -> Codec:
    return resolve_codec(tag, framing=framing)


def in_worker(fn: Callable, tag: str, framing: str, *args, **kwargs):
    """Run ``fn(codec, *args, **kwargs)`` in a pool worker with the codec
    rebuilt from ``tag`` and ``framing``. Returns ``(value, cpu_s)``, the
    worker's CPU seconds over the call; exceptions reach the caller with
    their types."""
    cpu0 = time.process_time()
    value = fn(_codec(tag, framing), *args, **kwargs)
    return value, time.process_time() - cpu0


def usable_cpus() -> int:
    """CPUs this process may run on (its affinity mask where the platform
    has one)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # pragma: no cover - platforms without affinity
        return os.cpu_count() or 1


def start_pool(max_workers: int) -> Optional[ProcessPoolExecutor]:
    """A pool of ``min(max_workers, usable CPUs)`` workers, or None with
    fewer than 2 usable CPUs (a worker would only take the parent's core).

    Workers are spawned, never forked: the parent may hold threads and a
    loaded accelerator runtime. Each gets one no-op at once, so their
    start-up overlaps the caller's own and the first decode finds them
    ready; nothing here waits for it.
    """
    cpus = usable_cpus()
    if cpus < 2:
        return None
    n = min(max_workers, cpus)
    pool = ProcessPoolExecutor(n, mp_context=multiprocessing.get_context("spawn"))
    for _ in range(n):
        pool.submit(os.getpid)
    return pool


# -- helpers ------------------------------------------------------------------


def _clocked(spent: List[float], it: Iterator[int]):
    """Iterate ``it``, adding the wall time spent inside each step (not the
    consumer's work between steps) to ``spent[0]``."""
    t0 = time.perf_counter()
    while True:
        try:
            item = next(it)
        except StopIteration:
            spent[0] += time.perf_counter() - t0
            return
        spent[0] += time.perf_counter() - t0
        yield item
        t0 = time.perf_counter()


def offset_result(res: DecodeResult, base_bits: int) -> DecodeResult:
    """Translate a buffer-local DecodeResult to global bit offsets."""
    if base_bits == 0:
        return res
    res.start_bit += base_bits
    res.end_bit += base_bits
    for b in res.blocks:
        b.bit_offset += base_bits
    for me in res.member_ends:
        me.footer_end_bit += base_bits
    for ms in res.member_starts:
        ms.header_start_bit += base_bits
        ms.deflate_start_bit += base_bits
    return res
