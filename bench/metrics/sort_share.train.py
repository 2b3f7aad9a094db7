"""Device time of sort ops over device busy time, in %.  Matches ops
named ``sort`` or ``sort.<n>`` (XLA's sort, which the WASH plan's
permutations lower to); nothing is read where no sort ran."""

import re

SORT = re.compile(r"^sort(\.\d+)?$")


def read(ctx):
    t = ctx.trace
    if t is None or t.op_count(lambda op: SORT.match(op.name)) == 0:
        return None
    return 100.0 * t.op_seconds(lambda op: bool(SORT.match(op.name))) / t.busy_s()
