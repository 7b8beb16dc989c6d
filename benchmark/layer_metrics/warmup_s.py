"""Layer: programs. Set-up minus staging: import, building the deployment
and the warm-up passes, i.e. what a restarted worker's first user waits
through (the reference's answers are computed after the window and are in
neither)."""


def read(run: dict):
    s = run["setup"]
    return s["setup_s"] - s["stage_s"] if s else None
