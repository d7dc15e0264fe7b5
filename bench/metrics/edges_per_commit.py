"""Edges per insert commit the server dispatched inside the window
(``ServerStats.edges_committed / commit_batches``, window deltas)."""


def read(facts):
    srv = facts["server"]
    if not srv["commit_batches"]:
        return None
    return srv["edges_committed"] / srv["commit_batches"]
