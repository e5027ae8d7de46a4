"""MB of C the guard copied to the host per completed call: the program's
count of the bytes each sweep copied, as its ``shiro.guard`` span carries
it in ``host_bytes`` (program span; ``stats()["guard_host_bytes"]`` sums
the same count)."""
from chipbench import scopes


def read(rec):
    b = scopes.span_stat(rec, "shiro.guard", "host_bytes", "call")
    return None if b is None else b / 1e6
