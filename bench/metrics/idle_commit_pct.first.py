"""Host commit in first full backups, every chunk new: seconds in which the
device ran nothing while the service was inside a ``phase.commit`` span
(the commit of each object with its nested spans), over the traced
window."""

SPAN = "phase.commit"


def read(rec):
    t = rec["trace"]
    if not t or t["window_s"] <= 0 or SPAN not in t.get("idle_under", {}):
        return None
    return 100.0 * t["idle_under"][SPAN] / t["window_s"]


CASE = ({"trace": {"idle_under": {"phase.commit": 0.4,
                                  "commit.object": 0.35}}},
        100.0 * 0.4 / 1.0)
