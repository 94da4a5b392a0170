"""Whole-checkpoint restores back to back.  The mix has no arrivals: its
parameters say how the weights are laid over the chips (``mesh``), how many
restores warm up, and whether the previous copy is dropped before the next
restore starts or held until the next has landed (``hold_previous``: a
replica that keeps serving from the old weights while the new ones arrive)."""


def schedule(traffic: dict, seed: int, seconds: float) -> dict:
    return {"mesh": traffic.get("mesh"),
            "warm_restores": int(traffic.get("warm_restores", 1)),
            "hold_previous": bool(traffic.get("hold_previous", False))}
