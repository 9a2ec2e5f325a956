"""Seconds of the offline schedule search (core/gradient_search.py) in set-up."""


def read(run):
    return run.search_s
