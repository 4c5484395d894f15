"""Gossip executor on the device: busy time (union of program
executions) on the busiest chip over the traced window, per trial, in
ms."""


def read(run):
    tr = run["trace"]
    if not tr["busy_s"]:
        return None
    return 1e3 * max(tr["busy_s"].values()) / tr["trials"]
