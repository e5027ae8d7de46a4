"""Host ms per completed call in the front door's ``shiro.dispatch`` span:
validation, placement, the B copy, the executable lookup and the launch
(program span)."""
from chipbench import scopes


def read(rec):
    return scopes.span_ms(rec, "shiro.dispatch", "call")
