"""Host seconds per call from the sampler's dispatch until the L_max step's
edge mask is ready, the span ``connectit.sample``, averaged over the
window's calls (``ConnectivityStats.sample_s``). None where the program
keeps no such field."""


def read(facts):
    values = [getattr(s, "sample_s", None) for s in facts["calls"]]
    if not values or None in values:
        return None
    return sum(values) / len(values)
