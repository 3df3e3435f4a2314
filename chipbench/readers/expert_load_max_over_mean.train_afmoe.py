"""The fullest expert's rows over the mean, in the expert layer where
that is largest: from the layers' sown ``expert_counts`` on the check's
sequence. 1.0 is a perfectly even router; the grouped products' tail
tiles grow with it."""


def read(run):
    return (run.get("check") or {}).get("expert_load_max_over_mean")
