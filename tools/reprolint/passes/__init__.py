"""Pass registry. Every pass module exports RULE (id), DOC (one-liner)
and ``check(mod: ParsedModule) -> Iterable[Finding]``; registration here
is what makes a pass exist (the CLI, the docs checker and the test corpus
all enumerate this dict)."""
from tools.reprolint.passes import journal, layering, leases, locks

PASSES = {p.RULE: p for p in (leases, locks, journal, layering)}

assert len(PASSES) == 4, "pass RULE ids must be unique"
