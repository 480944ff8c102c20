"""Recall@10 of the answers the check compared against the exact
reference over the rows live for each of them."""


def read(run):
    return run.checks["recall_at_10"]["value"]
