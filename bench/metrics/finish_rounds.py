"""Finish-phase rounds per call, averaged over the window's calls
(``ConnectivityStats.finish_rounds``)."""


def read(facts):
    calls = facts["calls"]
    return sum(s.finish_rounds for s in calls) / len(calls)
