"""Rule-based log analysis: error extraction, root cause, resume verdict.

Reference analog: ``attribution/log_analyzer/`` (LogSage + langchain LLM).
The always-available layer here is a rule engine tuned for JAX/TPU failure
modes; an LLM backend can be plugged in as ``llm_fn(prompt) -> str`` and is
consulted only when rules are inconclusive (same layering the reference
uses — its LLM deps are optional extras).

Categories and their restart policy:

=================  ===========================================  ==========
category           signature examples                           resume?
=================  ===========================================  ==========
device_error       "TPU initialization failed", RESOURCE_        yes (new
                   EXHAUSTED: HBM, halted, DMA error             chip/node)
oom_host           MemoryError, Killed (oom-kill)                no
oom_hbm            RESOURCE_EXHAUSTED ... hbm / allocating       no
numerics           loss is NaN/Inf assertions                    no
data               FileNotFoundError/dataset errors              no
preemption         SIGTERM from scheduler, preemption notice     yes
network            DCN/collective timeout, socket errors         yes
hang_kill          tpurx hang detection kill markers             yes
user_code          generic Python traceback                      no
unknown            nothing matched                               yes
=================  ===========================================  ==========
"""

from __future__ import annotations

import dataclasses
import enum
import re
from typing import Callable, Dict, List, Optional, Tuple

from ..utils.logging import get_logger
from .base import AttributionPipeline, AttributionResult

log = get_logger("log_analyzer")


class FailureCategory(str, enum.Enum):
    DEVICE_ERROR = "device_error"
    OOM_HOST = "oom_host"
    OOM_HBM = "oom_hbm"
    NUMERICS = "numerics"
    DATA = "data"
    PREEMPTION = "preemption"
    NETWORK = "network"
    HANG_KILL = "hang_kill"
    USER_CODE = "user_code"
    UNKNOWN = "unknown"


@dataclasses.dataclass
class AnalysisVerdict:
    category: FailureCategory
    should_resume: bool
    confidence: float
    culprit_ranks: List[int]
    evidence: List[str]
    summary: str


# (category, resume, confidence, patterns) — first match wins per line;
# highest-confidence category across lines wins overall.
_RULES: List[Tuple[FailureCategory, bool, float, List[str]]] = [
    (FailureCategory.OOM_HBM, False, 0.95, [
        r"RESOURCE_EXHAUSTED.{0,120}(hbm|HBM|memory)",
        r"Out of memory while trying to allocate",
        r"XlaRuntimeError.{0,80}RESOURCE_EXHAUSTED",
    ]),
    (FailureCategory.OOM_HOST, False, 0.9, [
        r"\bMemoryError\b",
        r"oom-kill|Out of memory: Killed process|oom_reaper",
    ]),
    (FailureCategory.DEVICE_ERROR, True, 0.9, [
        r"TPU.{0,60}(initialization failed|halted|unavailable|unhealthy)",
        r"(DMA|SparseCore|MXU).{0,40}error",
        r"failed to query tpu|libtpu.{0,40}(error|abort)",
        r"INTERNAL:.{0,80}(device|chip)",
    ]),
    (FailureCategory.HANG_KILL, True, 0.9, [
        r"hang detected.{0,120}terminating rank",
        r"wedged for .*killing",
        r"pod heartbeat stale",
    ]),
    (FailureCategory.NUMERICS, False, 0.85, [
        r"loss (is|became) (nan|inf)",
        r"\bNaN\b.{0,40}(loss|grad)",
        r"FloatingPointError",
    ]),
    (FailureCategory.PREEMPTION, True, 0.85, [
        r"preempt(ed|ion)",
        r"received SIGTERM.{0,60}(scheduler|maintenance)",
        r"DUE TO .*MAINTENANCE",
    ]),
    (FailureCategory.NETWORK, True, 0.8, [
        r"(DEADLINE_EXCEEDED|UNAVAILABLE):.{0,120}",
        r"collective.{0,60}timed? ?out",
        r"(ConnectionResetError|BrokenPipeError|ConnectionRefusedError)",
        r"store op \w+ (failed|timed out)",
    ]),
    (FailureCategory.DATA, False, 0.8, [
        r"FileNotFoundError",
        r"(dataset|tfrecord|arrayrecord).{0,60}(corrupt|missing|error)",
    ]),
    (FailureCategory.USER_CODE, False, 0.5, [
        r"Traceback \(most recent call last\)",
    ]),
]

_RANK_RE = re.compile(r"\[r(\d+)\]|rank[=\s](\d+)", re.IGNORECASE)


class LogAnalyzer:
    """``consult_llm`` modes (reference LogSage layering,
    ``log_analyzer/nvrx_logsage.py:12-40``):

    - ``"fallback"`` (default): LLM consulted only when no rule matched;
    - ``"always"``: LLM sees the rule verdict too and may confirm (confidence
      boost) or override it (override taken only when the LLM is MORE
      confident than the rules);
    - ``"never"``: rules only, even if ``llm_fn`` is set.
    """

    def __init__(
        self,
        llm_fn: Optional[Callable[[str], str]] = None,
        context_lines: int = 3,
        consult_llm: str = "fallback",
    ):
        if consult_llm not in ("never", "fallback", "always"):
            raise ValueError(f"consult_llm must be never|fallback|always, got {consult_llm!r}")
        self.llm_fn = llm_fn
        self.context_lines = context_lines
        self.consult_llm = consult_llm
        self.pipeline = AttributionPipeline(
            attribute=self._attribute,
            preprocess=[self._extract_errors],
            name="log_analyzer",
        )

    # -- stages ------------------------------------------------------------

    def _extract_errors(self, text: str, ctx: Dict) -> List[Tuple[int, str]]:
        """Return (line_no, line) candidates worth matching (error-ish)."""
        lines = text.splitlines()
        ctx["all_lines"] = lines
        interesting = []
        for i, line in enumerate(lines):
            if re.search(
                r"error|fail|abort|kill|exceed|exhaust|timeout|traceback|nan|preempt|hang|stale",
                line, re.IGNORECASE,
            ):
                interesting.append((i, line))
        ctx["n_candidates"] = len(interesting)
        return interesting

    def _attribute(self, candidates: List[Tuple[int, str]], ctx: Dict) -> AttributionResult:
        best: Optional[Tuple[FailureCategory, bool, float]] = None
        evidence: List[str] = []
        ranks: List[int] = []
        for lineno, line in candidates:
            for category, resume, conf, patterns in _RULES:
                if any(re.search(p, line, re.IGNORECASE) for p in patterns):
                    if best is None or conf > best[2]:
                        best = (category, resume, conf)
                    evidence.append(f"L{lineno}: {line.strip()[:240]}")
                    m = _RANK_RE.search(line)
                    if m:
                        rank = int(next(g for g in m.groups() if g is not None))
                        if rank not in ranks:
                            ranks.append(rank)
                    break
        llm_on = self.llm_fn is not None and self.consult_llm != "never"
        if best is None:
            if llm_on and candidates:
                llm = self._llm_attribute(candidates, ctx, rule_verdict=None)
                if llm is not None:
                    return llm
            return AttributionResult(
                category=FailureCategory.UNKNOWN.value,
                confidence=0.1,
                summary="no known failure signature found",
                should_resume=True,
            )
        category, resume, conf = best
        result = AttributionResult(
            category=category.value,
            confidence=conf,
            culprit_ranks=sorted(ranks),
            summary=f"{category.value} ({len(evidence)} matching lines)",
            evidence=evidence[:20],
            should_resume=resume,
        )
        if llm_on and self.consult_llm == "always":
            rule_verdict = {
                "category": result.category,
                "should_resume": result.should_resume,
                "confidence": result.confidence,
            }
            llm = self._llm_attribute(candidates, ctx, rule_verdict=rule_verdict)
            if llm is not None:
                if llm.category == result.category:
                    result.confidence = min(0.99, max(result.confidence, llm.confidence) + 0.05)
                    result.summary += f"; llm concurs: {llm.summary}"
                    result.culprit_ranks = sorted(
                        set(result.culprit_ranks) | set(llm.culprit_ranks)
                    )
                elif (
                    llm.category != FailureCategory.UNKNOWN.value
                    and llm.confidence > result.confidence
                ):
                    # a hallucinated (outside the known categories -> unknown) category
                    # must never displace a concrete rule verdict
                    llm.summary += f" (overrode rules' {result.category})"
                    llm.evidence = result.evidence
                    result = llm
                ctx["llm_consulted"] = True
        return result

    def _llm_attribute(self, candidates, ctx, rule_verdict=None) -> Optional[AttributionResult]:
        from .llm import build_attribution_prompt, parse_attribution_response

        try:
            answer = self.llm_fn(build_attribution_prompt(candidates, rule_verdict))
            parsed = parse_attribution_response(answer)
        except Exception:  # noqa: BLE001
            log.exception("llm attribution failed; falling back to rules")
            return None
        if parsed is None:
            log.warning("unparseable llm attribution response: %.200s", answer)
            return None
        known = parsed["category"] in FailureCategory._value2member_map_
        return AttributionResult(
            category=parsed["category"] if known else FailureCategory.UNKNOWN.value,
            confidence=parsed["confidence"],
            culprit_ranks=parsed["culprit_ranks"],
            summary=parsed["reason"] or "llm attribution",
            should_resume=parsed["should_resume"],
            extra={"source": "llm"},
        )

    # -- public ------------------------------------------------------------

    def analyze_text(self, text: str) -> AnalysisVerdict:
        result = self.pipeline.run(text)
        return AnalysisVerdict(
            category=FailureCategory(result.category)
            if result.category in FailureCategory._value2member_map_
            else FailureCategory.UNKNOWN,
            should_resume=result.should_resume,
            confidence=result.confidence,
            culprit_ranks=result.culprit_ranks,
            evidence=result.evidence,
            summary=result.summary,
        )

    def analyze_file(self, path: str, tail_bytes: int = 1 << 20) -> AnalysisVerdict:
        with open(path, "rb") as f:
            f.seek(0, 2)
            size = f.tell()
            f.seek(max(0, size - tail_bytes))
            text = f.read().decode(errors="replace")
        return self.analyze_text(text)
