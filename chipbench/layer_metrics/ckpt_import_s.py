"""Checkpoint: seconds of the measured fit's last save (the final one, which
``final_save_s`` times from outside) in the program's phase span
``ckpt:import``: the lazy ``import orbax.checkpoint``, which
takes seconds in a process's first save on a cold machine (PERF.md) and
microseconds in a later one. ``ckpt_d2h_s`` +
``ckpt_import_s`` + ``ckpt_write_s`` is ``final_save_s``."""

from chipbench.trace import fit_spans


def read(run):
    return fit_spans.save_s("ckpt:import")
