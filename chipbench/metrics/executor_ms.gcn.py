"""Device ms per training step of the ops under the executor's
``shiro.spmm`` scope, forward and backward, mean over the chips."""
from chipbench import scopes


def read(rec):
    return scopes.scope_ms(rec, "spmm", "step")
