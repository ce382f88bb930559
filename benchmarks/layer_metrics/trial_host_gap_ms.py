"""Median host time from a round's last ``trial`` event (its rewards
are on the host) to the start of the next dispatch: the TPE's ask, the
decode, and the fsync'd trial log.  From the journal's own stamps; where
the journal's rate limit dropped the first dispatch of a round, the span
runs to the next one it kept, so read the median."""

from benchmarks.harness import trace as tr

META = {"layer": "search_scheduler", "unit": "ms", "source": "program_span",
        "moves": "search_trials_per_s"}


def read(obs):
    spans = [(b - a) * 1e3 for name, a, b in obs.host_spans
             if name == "between trials"]
    return tr.median(spans)
