"""Share of the traced window in which the device was idle while the server
held a request, in %: the idle seconds that the trace reduction names by a
``connectit.serve.*`` span (coalesce, commit or answer), over the window.
None without a trace, or where no idle gap is named by such a span (a
program without the serve spans)."""

PREFIX = "connectit.serve."


def read(facts):
    red = facts["trace"]
    if not red or red["window_s"] <= 0:
        return None
    held = [s for name, s in red["breakdown"]["idle_gaps"]
            if name.startswith(PREFIX)]
    if not held:
        return None
    return 100.0 * sum(held) / red["window_s"]
