"""Fixture: both suppression placements, each with a reason. Expected:
0 actionable findings, 2 suppressed."""


def standalone_comment(fs, extents):
    # reprolint: allow[lease-raw] fixture: comment line above covers the grant
    lease = fs.grant_lease(extents, ())
    return lease


def same_line(fs, extents):
    return fs.grant_lease((), extents)  # reprolint: allow[lease-raw] fixture: same-line suppression
