"""Model: the share of the trained tokens that the step's noise masked, from
the program's counter ``train_diffusion_tokens_total{masked|all}`` (summed on
the device inside the train step, fetched with each epoch's loss; whole
process, a calibration fit included). Only a masked token has a loss term
(weighed by 1 / t), so this is the share of the head's rows that carry
gradient: about 50 under t ~ U(0, 1], the mean of t. It counts with no size
of the configuration's; a program without the counter says nothing."""


def read(run):
    tokens = run["counters"].get("train_diffusion_tokens_total", {})
    if not tokens.get("all") or "masked" not in tokens:
        return None
    return 100.0 * tokens["masked"] / tokens["all"]
