"""Host ms per completed call in the front door's ``shiro.wait`` span: from
the launch's return until C is ready on the device, the part of the
device's work that the host waits for (program span)."""
from chipbench import scopes


def read(rec):
    return scopes.span_ms(rec, "shiro.wait", "call")
