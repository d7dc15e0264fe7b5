"""Host seconds per call of the host compaction (the edge mask and edges
copied to the host, indexed, padded and uploaded), the span
``connectit.compact``, averaged over the window's calls
(``ConnectivityStats.compact_s``). None where the program keeps no such
field."""


def read(facts):
    values = [getattr(s, "compact_s", None) for s in facts["calls"]]
    if not values or None in values:
        return None
    return sum(values) / len(values)
