"""Request objects, sampling parameters, and streamed outputs.

The engine's public output type is :class:`RequestOutput`: an immutable
per-iteration snapshot (delta tokens + cumulative output + finish state)
emitted by ``Engine.step`` — callers never see the engine's internal
:class:`Request` bookkeeping mutate under them.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import List, Optional, Sequence, Tuple

from .config import EngineError


class Status(enum.Enum):
    """Request lifecycle state (engine-internal)."""

    WAITING = "waiting"
    RUNNING = "running"
    #: evicted mid-decode by the block-growth engine (pool exhausted);
    #: the request sits at the *front* of the waiting queue, holds no
    #: blocks, and will be re-prefilled + replayed when space frees up
    PREEMPTED = "preempted"
    FINISHED = "finished"


class FinishReason(str, enum.Enum):
    """Why a request retired.  ``str``-valued so ``out.finish_reason ==
    "eos"`` works without importing the enum."""
    EOS = "eos"            # hit params.eos_id
    LENGTH = "length"      # produced max_new_tokens
    STOP = "stop"          # hit one of params.stop_token_ids
    ABORT = "abort"        # cancelled via Engine.abort
    CONTEXT = "context"    # slot context (max_seq / reserved blocks) full


@dataclasses.dataclass
class SamplingParams:
    """Per-request decode controls.

    ``temperature == 0`` → greedy; ``top_k == 0`` → no truncation.
    ``eos_id``/``stop_token_ids`` finish a request only after
    ``min_new_tokens`` tokens have been produced (the stop token itself is
    included in the output).  ``seed`` pins the request's private RNG
    stream: two submissions with the same prompt, params, and seed sample
    identical tokens regardless of what else shares the batch; ``None``
    draws a fresh stream per submission.
    """
    temperature: float = 0.0
    top_k: int = 0
    max_new_tokens: int = 32
    min_new_tokens: int = 0
    eos_id: Optional[int] = None
    stop_token_ids: Tuple[int, ...] = ()
    seed: Optional[int] = None

    def __post_init__(self):
        if self.temperature < 0:
            raise EngineError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise EngineError(f"top_k must be >= 0, got {self.top_k}")
        if self.max_new_tokens < 1:
            raise EngineError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if not 0 <= self.min_new_tokens <= self.max_new_tokens:
            raise EngineError(
                f"min_new_tokens={self.min_new_tokens} must lie in "
                f"[0, max_new_tokens={self.max_new_tokens}]")
        if isinstance(self.stop_token_ids, (str, bytes)) or \
                not isinstance(self.stop_token_ids, Sequence):
            raise EngineError("stop_token_ids must be a sequence of ints")
        try:
            self.stop_token_ids = tuple(int(t) for t in self.stop_token_ids)
        except (TypeError, ValueError) as e:
            raise EngineError(
                f"stop_token_ids must be a sequence of ints: {e}") from e

    def stops_on(self, token: int) -> Optional[FinishReason]:
        """Finish reason the token triggers (eos/stop), or None."""
        if self.eos_id is not None and token == self.eos_id:
            return FinishReason.EOS
        if token in self.stop_token_ids:
            return FinishReason.STOP
        return None


@dataclasses.dataclass
class RequestOutput:
    """One streamed increment of a request's output.

    ``new_token_ids`` are the tokens produced *this* engine iteration
    (one per decode step; empty for a pure finish notification such as an
    abort); ``output_token_ids`` is the cumulative output so far.  When
    ``finished`` is True, ``finish_reason`` is set and the timing fields
    carry the request's final metrics.  ``cached_tokens`` counts the
    prompt tokens whose KV was served from the prefix cache instead of
    being recomputed (always 0 unless the engine runs with
    ``enable_prefix_caching``).  ``num_preemptions`` counts how many
    times the request was evicted and recovered by the block-growth
    engine (always 0 unless ``enable_block_growth``); the token stream
    is unaffected — preemption recovery is byte-exact — but latency is
    not, so the count is surfaced for observability.
    ``replay_iterations`` counts the non-emitting engine iterations
    spent re-feeding already-produced tokens after preemptions (the
    one-chunk recovery path keeps this O(produced / prefill_chunk) per
    preemption instead of O(produced)), and ``recovery_time`` is the
    total wall-clock seconds between each eviction and the request's
    next emission.  ``queue_time`` is the seconds the request waited
    between arrival and its first admission to a slot (None until it is
    admitted; a preemption does not move it).
    """

    rid: int
    prompt_len: int
    new_token_ids: List[int]
    output_token_ids: List[int]
    finished: bool = False
    finish_reason: Optional[FinishReason] = None
    cached_tokens: int = 0
    num_preemptions: int = 0
    replay_iterations: int = 0
    recovery_time: float = 0.0
    queue_time: Optional[float] = None

    # final metrics (populated on the finished output) -------------------
    ttft: Optional[float] = None        # first-token latency (s)
    latency: Optional[float] = None     # end-to-end latency (s)


@dataclasses.dataclass
class Request:
    """Engine-internal lifecycle record (not part of the public stream
    surface; the engine emits :class:`RequestOutput` snapshots instead)."""
    rid: int
    prompt: List[int]
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    arrival_time: float = 0.0
    #: resolved RNG seed for this request's private sampling stream
    #: (params.seed, or a per-submission default derived by the engine)
    seed: int = 0

    # lifecycle (filled by the engine) ----------------------------------
    status: Status = Status.WAITING
    slot: int = -1
    #: tokens *fed* through the model so far — the unified feed cursor.
    #: prompt + produced output form one logical token stream E; ``pos``
    #: counts how many of its tokens have been run through decode_step
    #: (admission seeds it at the prefix-cache skip).  At the k-th
    #: emission ``pos == prompt_len - 1 + k``, which is exactly the
    #: slot's newest written KV position — the main loop never syncs the
    #: device positions array.
    pos: int = 0
    output: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[FinishReason] = None
    #: first admission to a slot (engine clock); re-admission after a
    #: preemption leaves it
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None    # TTFT measurement
    finish_time: Optional[float] = None
    #: prompt tokens served from the prefix cache (reported on outputs)
    cached_tokens: int = 0
    #: prompt tokens whose staged prefill is skipped on a prefix hit —
    #: the block-aligned shared extent, or ``prompt_len - 1`` after a
    #: copy-on-write tail materialization (engine-internal)
    prefix_skip: int = 0
    #: chain hashes of the prompt's full blocks, computed once at the
    #: admission gate and reused for registration (engine-internal)
    prefix_hashes: List[bytes] = dataclasses.field(default_factory=list)
    #: times this request was preempted by the block-growth engine
    num_preemptions: int = 0
    #: non-emitting iterations spent re-feeding already-produced tokens
    #: after preemptions (one forced multi-token chunk per iteration —
    #: recovery is O(produced / prefill_chunk) steps, not O(produced))
    replay_iterations: int = 0
    #: cumulative eviction → next-emission wall-clock seconds
    recovery_time: float = 0.0
    #: set at eviction, closed out at the next emission (engine-internal)
    recovery_started: Optional[float] = None
    #: prompt blocks still to be published in the prefix index at the
    #: request's first emission — registration waits until the blocks
    #: below the frontier are fully written (engine-internal)
    needs_register: bool = False

    @property
    def ttft(self) -> Optional[float]:
        """First-token latency in seconds (None until measured)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    @property
    def queue_time(self) -> Optional[float]:
        """Seconds from arrival to first admission (None until then)."""
        if self.admit_time is None:
            return None
        return self.admit_time - self.arrival_time

    @property
    def latency(self) -> Optional[float]:
        """End-to-end latency in seconds (None until finished)."""
        if self.finish_time is None:
            return None
        return self.finish_time - self.arrival_time

    @property
    def done(self) -> bool:
        """True once the request has finished (any reason)."""
        return self.status == Status.FINISHED

    def make_output(self, new_tokens: List[int]) -> RequestOutput:
        """Snapshot this request's state as a public RequestOutput."""
        done = self.done
        return RequestOutput(
            rid=self.rid, prompt_len=len(self.prompt),
            new_token_ids=list(new_tokens),
            output_token_ids=list(self.output),
            finished=done, finish_reason=self.finish_reason if done else None,
            cached_tokens=self.cached_tokens,
            num_preemptions=self.num_preemptions,
            replay_iterations=self.replay_iterations,
            recovery_time=self.recovery_time,
            queue_time=self.queue_time,
            ttft=self.ttft if done else None,
            latency=self.latency if done else None)
