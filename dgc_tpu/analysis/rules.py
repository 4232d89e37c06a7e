"""Rule catalog + allowlist for the dgclint AST layer.

Every rule is a static description; the detection logic lives in
:mod:`dgc_tpu.analysis.astlint` (one visitor, dispatching per rule id).
Rules target the hazards that silently break the DGC compiled-step
contract (ISSUE 3; docs/ANALYSIS.md has the full catalog with examples):

* a host sync inside jitted scope turns the paper's "one sparse exchange
  per step" into a device round-trip per call site;
* a Python branch on a tracer either crashes at trace time or — worse —
  silently bakes one side into the compiled program;
* a float64 literal upcasts whole fusions (TPUs emulate f64 in software);
* host entropy (``time.time``, ``np.random``) freezes into the trace;
* a jit that threads dead state without ``donate_argnums`` doubles HBM.

Audited exceptions are recorded in ``allowlist.toml`` next to this file
(rule + file glob + source-line substring + one-line justification), or
inline with a ``# dgclint: ok`` / ``# dgclint: ok[rule-id]`` comment for
fixture-style single-line waivers.
"""

import fnmatch
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Rule", "RULES", "VERIFY_PASSES", "RACE_RULES", "RULES_BY_ID",
           "Finding", "Allowlist", "load_allowlist",
           "DEFAULT_ALLOWLIST_PATH"]

DEFAULT_ALLOWLIST_PATH = os.path.join(os.path.dirname(__file__),
                                      "allowlist.toml")


@dataclass(frozen=True)
class Rule:
    id: str             # stable kebab-case id, used in allowlists/waivers
    code: str           # short numeric code for terse output (DGC1xx)
    summary: str        # one line, shown next to each finding
    traced_only: bool   # rule only fires inside traced (jitted) scope


RULES: Tuple[Rule, ...] = (
    Rule("host-sync", "DGC101",
         "host-synchronizing call reachable from jitted scope "
         "(float()/int() on a tracer, .item(), np.asarray, "
         "jax.device_get, print)", True),
    Rule("tracer-branch", "DGC102",
         "Python if/while/assert on a tracer-valued expression in "
         "jitted scope (use lax.cond/select or hoist to static)", True),
    Rule("f64-dtype", "DGC103",
         "float64 literal or dtype drift (TPU emulates f64; the DGC "
         "pipeline contract is f32 end-to-end)", False),
    Rule("static-argnums", "DGC104",
         "jax.jit static_argnums/static_argnames must be a hashable "
         "literal (int/str or tuple thereof), not a list or a computed "
         "expression", False),
    Rule("missing-donate", "DGC105",
         "jitted state-threading function without donate_argnums: the "
         "dead input buffer doubles peak HBM", False),
    Rule("host-entropy", "DGC106",
         "host time/RNG in traced code (time.time, np.random, random): "
         "the value freezes into the compiled program", True),
    Rule("sync-in-loop", "DGC107",
         "per-iteration host conversion on step outputs inside a driver "
         "loop (float()/int()/.item()/device_get): stalls the dispatch "
         "pipeline every iteration — batch the reads after the loop",
         False),
    Rule("mutable-closure", "DGC108",
         "jitted function reads a module-level flag that some function "
         "mutates via `global`: the first trace bakes the flag's value "
         "into the jaxpr cache, so later mutations are silently ignored "
         "(pass it as a static arg or rebuild the closure per value)",
         True),
)

#: dgcver verifier passes (docs/ANALYSIS.md §Verifier). Kept separate
#: from RULES — the AST linter must not expect fixtures or dispatch for
#: them — but registered in RULES_BY_ID so allowlist.toml entries and
#: Finding.format() work identically for both layers.
VERIFY_PASSES: Tuple[Rule, ...] = (
    Rule("collective-axis", "DGCV01",
         "collective runs over an axis missing from the AxisPolicy, has "
         "no named axis at all, or pushes an axis past its per-axis "
         "collective budget", True),
    Rule("dtype-flow", "DGCV02",
         "truncating cast (f32->bf16/f16/int) on a value tainted by an "
         "f32 source (residual, momentum, guards, loss) whose narrow "
         "flow never crosses a collective — precision silently lost "
         "outside a wire lane", True),
    Rule("donation-liveness", "DGCV03",
         "state-shaped argument is dead after its first read but not "
         "donated: the input buffer stays resident and doubles peak "
         "HBM for that array", True),
    Rule("ef-conservation", "DGCV04",
         "error-feedback conservation broken: a selected gradient "
         "element's flow does not reach both the wire payload and a "
         "transmit-record/residual fold-back sink", True),
)

#: dgcmc race-lint rules (docs/ANALYSIS.md §Layer 4). Like VERIFY_PASSES,
#: kept separate from RULES — detection lives in
#: :mod:`dgc_tpu.analysis.racelint`, with its own pos/neg fixture pairs —
#: but registered in RULES_BY_ID so allowlist.toml entries, inline
#: waivers and Finding.format() work identically across layers.
RACE_RULES: Tuple[Rule, ...] = (
    Rule("thread-shared-state", "DGC201",
         "module/instance state written by a spawned thread and accessed "
         "by another thread with no shared lock on every access — the "
         "Eraser lockset condition (guard with one Lock, or hand the "
         "value over a queue/Event)", False),
    Rule("thread-crash-file", "DGC202",
         "a spawned thread and a signal/atexit crash handler write the "
         "same file — a crash mid-write interleaves the two writers on "
         "one path (route both through one atomic publisher)", False),
    Rule("thread-traced-state", "DGC203",
         "a spawned thread mutates state that traced (jitted) scope "
         "reads: the first trace bakes the value into the jaxpr cache "
         "and the thread's updates are silently ignored (thread the "
         "value as a step argument)", False),
    Rule("thread-no-join", "DGC204",
         "non-daemon Thread never joined in its module: interpreter "
         "shutdown blocks on it forever (daemon=True, or join with a "
         "timeout)", False),
)

RULES_BY_ID: Dict[str, Rule] = {
    r.id: r for r in RULES + VERIFY_PASSES + RACE_RULES}

#: inline waivers: ``# dgclint: ok`` / ``# dgclint: ok[id,id]`` for the
#: AST layer, ``# dgcver: ok`` / ``# dgcver: ok[pass-id]`` for verifier
#: findings (matched against the source line the jaxpr provenance names)
_WAIVER_RES = {
    "dgclint": re.compile(r"#\s*dgclint:\s*ok(?:\[([a-z0-9_,\- ]+)\])?"),
    "dgcver": re.compile(r"#\s*dgcver:\s*ok(?:\[([a-z0-9_,\- ]+)\])?"),
}
_WAIVER_RE = _WAIVER_RES["dgclint"]


@dataclass
class Finding:
    rule: str
    path: str           # posix path relative to the lint root
    line: int
    col: int
    snippet: str        # the offending source line, stripped
    message: str
    allowed: bool = False
    allowed_by: str = ""   # "inline" or the allowlist reason

    def format(self) -> str:
        mark = f"  [allowed: {self.allowed_by}]" if self.allowed else ""
        code = RULES_BY_ID[self.rule].code
        return (f"{self.path}:{self.line}:{self.col}: {code} "
                f"[{self.rule}] {self.message}{mark}\n"
                f"    {self.snippet}")


@dataclass
class Allowlist:
    """Audited exceptions: entries match (rule, file glob, line substring).

    ``contains`` is matched against the offending *source line* — robust
    across line-number drift, unlike path:line pins. An empty ``contains``
    allows the rule for the whole file (use sparingly)."""
    entries: List[dict] = field(default_factory=list)

    def match(self, finding: Finding) -> Optional[str]:
        for e in self.entries:
            if e.get("rule") and e["rule"] != finding.rule:
                continue
            if not fnmatch.fnmatch(finding.path, e.get("file", "*")):
                continue
            contains = e.get("contains", "")
            if contains and contains not in finding.snippet:
                continue
            return e.get("reason", "allowlisted")
        return None

    @staticmethod
    def inline_waiver(source_line: str, rule: str,
                      tool: str = "dgclint") -> bool:
        m = _WAIVER_RES[tool].search(source_line)
        if not m:
            return False
        if m.group(1) is None:
            return True
        ids = {s.strip() for s in m.group(1).split(",")}
        return rule in ids


def load_allowlist(path: Optional[str] = None) -> Allowlist:
    """Parse ``allowlist.toml``."""
    path = path or DEFAULT_ALLOWLIST_PATH
    if not os.path.exists(path):
        return Allowlist()
    import tomllib
    with open(path, "rb") as f:
        data = tomllib.load(f)
    entries = list(data.get("allow", []))
    for e in entries:
        if "reason" not in e or not str(e["reason"]).strip():
            raise ValueError(
                f"allowlist entry {e} lacks a reason — every audited "
                "exception must carry a one-line justification")
        if e.get("rule") and e["rule"] not in RULES_BY_ID:
            raise ValueError(f"allowlist entry names unknown rule "
                             f"{e['rule']!r} (known: "
                             f"{sorted(RULES_BY_ID)})")
    return Allowlist(entries)
