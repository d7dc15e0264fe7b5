"""Share of the graph's directed edges that reach the finish phase after
k-out sampling and the host compaction, over the window's calls, in %
(``ConnectivityStats.edges_finish / edges_total``)."""


def read(facts):
    calls = facts["calls"]
    total = sum(s.edges_total for s in calls)
    return 100.0 * sum(s.edges_finish for s in calls) / total
