"""From the start of run.py to the first query of the window: import,
staging, building the deployment, warm-up passes (compilation included)."""


def read(run: dict):
    return run["setup"].get("setup_s")
