"""Inference request lifecycle (paper §II-C, Fig. 2)."""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import List, Optional


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"     # scheduled for the next mixed stage
    DECODE = "decode"
    DONE = "done"           # completed generation (eos / length)
    CANCELLED = "cancelled"  # caller cancel / queue shed / admission reject
    EXPIRED = "expired"      # deadline or TTFT SLO passed


#: states a request can never leave; ``finish_reason`` says why it got there
TERMINAL_STATES = frozenset({RequestState.DONE, RequestState.CANCELLED,
                             RequestState.EXPIRED})


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    arrival_time: float = 0.0
    eos_id: Optional[int] = None
    state: RequestState = RequestState.QUEUED
    slot: int = -1
    output: List[int] = field(default_factory=list)
    # robustness (PR 6): absolute finish deadline and first-token SLO
    # (seconds after arrival), on the same clock as ``arrival_time``. The
    # engine's per-stage expiry sweep transitions past-deadline requests to
    # EXPIRED so dead work never occupies a slot or a page.
    deadline: Optional[float] = None
    ttft_slo: Optional[float] = None
    # scheduling priority (PR 7): HIGHER values are more important — they
    # admit ahead of lower-priority queued work and are evicted last under
    # capacity pressure (preemption victims are picked lowest-priority
    # first). The fleet boosts failover re-submissions so a request that
    # already survived a replica death is not immediately re-evicted.
    priority: int = 0
    # priority aging (PR 8): stages formed while this request sat in the
    # admission queue. With ``aging_rounds=K`` the scheduler promotes the
    # *effective* priority by one band per K skipped rounds so a starved
    # low band eventually admits under sustained high-priority load.
    # ``queue_seq`` is the scheduler's submit sequence number — the FIFO
    # tiebreak within an effective-priority band when aging re-sorts.
    aging_skips: int = 0
    queue_seq: int = 0
    # why the request reached a terminal state: "stop" (eos), "length",
    # "cancelled", "shed", "rejected", "expired" or "lost" (replica died
    # with failover disabled); None while live.
    finish_reason: Optional[str] = None
    # chunked prefill (scheduler-owned): positions [0, prefill_pos) have
    # been processed and their KV written; prefill_target is frozen at
    # admission (prompt + recompute-replayed output — it must not drift when
    # the final chunk's sampled token lands in ``output``). Reset on
    # recompute-preemption.
    prefill_pos: int = 0
    prefill_target: Optional[int] = None
    # preemption (paper SVIII-C): host-saved KV (migrate) / retry marker
    saved_cache: Optional[list] = None
    was_preempted: bool = False
    # prefix sharing (paged + prefix_share): page ids matched & pinned at
    # submit time — mapped into the slot's block table at admission
    # (KVManager.adopt_prefix), after which this clears. prefill_pos is set
    # to the first unshared position so chunk spans skip the shared prefix.
    # match_version caches the KVManager.index_version the last match ran
    # against, so queued heads are only re-matched when the index changed.
    shared_pages: Optional[List[int]] = None
    match_version: int = -1
    # time.monotonic() when the request last entered the admission queue
    # (submit, preemption, an aborted admission); its wait until it claims
    # a KV slot is one ``engine.queue`` span
    queued_at: Optional[float] = None
    # latency bookkeeping (T2FT / TBT / E2E, paper Fig. 2)
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    token_times: List[float] = field(default_factory=list)

    @property
    def l_in(self) -> int:
        return len(self.prompt)

    def token_stream(self, upto: Optional[int] = None) -> List[int]:
        """The request's processed token stream — prompt followed by
        generated tokens (what prefill/replay covers and what the prefix
        index keys on). One definition for every consumer."""
        toks = list(self.prompt) + list(self.output)
        return toks if upto is None else toks[:upto]

    @property
    def prefill_total(self) -> int:
        """Positions prefill must cover before decode resumes: the prompt,
        plus any already-generated tokens for a recompute-preempted request
        (its KV was dropped and must be rebuilt, paper SVIII-C). Frozen into
        ``prefill_target`` at admission."""
        if self.prefill_target is not None:
            return self.prefill_target
        return len(self.prompt) + len(self.output)

    @property
    def prefill_done(self) -> bool:
        return (self.prefill_target is not None
                and self.prefill_pos >= self.prefill_target)

    @property
    def done(self) -> bool:
        """Terminal — completed, cancelled or expired. ``completed``
        distinguishes requests that actually finished generating."""
        return self.state in TERMINAL_STATES

    @property
    def completed(self) -> bool:
        return self.state == RequestState.DONE

    def past_deadline(self, now: float) -> bool:
        """True when ``now`` is beyond this request's finish deadline, or
        its TTFT SLO has lapsed without a first token. Terminal requests
        never re-expire."""
        if self.state in TERMINAL_STATES:
            return False
        if self.deadline is not None and now >= self.deadline:
            return True
        return (self.ttft_slo is not None and self.first_token_time is None
                and now >= self.arrival_time + self.ttft_slo)

    def finish(self, reason: str, now: float) -> None:
        """Abnormal termination: cancel / shed / reject / expire. The caller
        (the engine) is responsible for releasing slots, pages and pins."""
        self.state = (RequestState.EXPIRED if reason == "expired"
                      else RequestState.CANCELLED)
        self.finish_reason = reason
        self.finish_time = now

    def record_token(self, token: int, now: float) -> None:
        self.output.append(token)
        self.token_times.append(now)
        if self.first_token_time is None:
            self.first_token_time = now
        if self.eos_id is not None and token == self.eos_id:
            self.state = RequestState.DONE
            self.finish_reason = "stop"
            self.finish_time = now
        elif len(self.output) >= self.max_new_tokens:
            self.state = RequestState.DONE
            self.finish_reason = "length"
            self.finish_time = now

    # ---- metrics ----
    def t2ft(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    def e2e(self) -> Optional[float]:
        if self.finish_time is None:
            return None
        return self.finish_time - self.arrival_time

    def tbts(self) -> List[float]:
        return [b - a for a, b in zip(self.token_times, self.token_times[1:])]
