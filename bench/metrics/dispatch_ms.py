"""Median over the window's calls of the host time the entry takes to
return, before the harness blocks on its result (ms)."""
import statistics


def read(run):
    return statistics.median((back - sent) * 1e3
                             for sent, back, _ in run.calls)
