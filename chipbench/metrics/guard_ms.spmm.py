"""Host ms per completed call in the front door's ``shiro.guard`` span: the
guard's copy of C to the host and its isfinite sweep (program span)."""
from chipbench import scopes


def read(rec):
    return scopes.span_ms(rec, "shiro.guard", "call")
