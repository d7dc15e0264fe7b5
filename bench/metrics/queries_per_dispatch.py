"""Query pairs per device dispatch inside the window
(``ServerStats.queries_answered / query_batches``, window deltas)."""


def read(facts):
    srv = facts["server"]
    if not srv["query_batches"]:
        return None
    return srv["queries_answered"] / srv["query_batches"]
