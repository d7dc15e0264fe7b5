"""Host seconds per call from the finish's dispatch until its round count is
on the host (finish, closing compress and canonical labels), the span
``connectit.finish``, averaged over the window's calls
(``ConnectivityStats.finish_s``). None where the program keeps no such
field."""


def read(facts):
    values = [getattr(s, "finish_s", None) for s in facts["calls"]]
    if not values or None in values:
        return None
    return sum(values) / len(values)
