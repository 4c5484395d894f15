"""Trials completed in the window over the window's seconds, from the
first call's start to the last call's end (host clock)."""


def read(run):
    calls = run["calls"]
    span = calls[-1]["end"] - calls[0]["start"]
    return sum(c["trials"] for c in calls) / span
