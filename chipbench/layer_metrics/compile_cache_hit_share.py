"""Train loop: of the ``jit:compile`` spans of the measured fit (at any depth
under its ``fit:run``), the share whose ``cache`` is ``hit``: the persistent
compile cache served the program. A warm run should read near 100; a source
line that moved (it is part of a lowered module's text) shows as less."""

from chipbench.trace import build_spans


def read(run):
    return build_spans.cache_hit_share()
