"""Checkpoint: seconds of the measured fit's last save (the final one, which
``final_save_s`` times from outside) in the program's phase span
``ckpt:write``: orbax writing the host state to disk,
the ``extra.json`` sidecar and retention pruning. ``ckpt_d2h_s`` +
``ckpt_import_s`` + ``ckpt_write_s`` is ``final_save_s``."""

from chipbench.trace import fit_spans


def read(run):
    return fit_spans.save_s("ckpt:write")
