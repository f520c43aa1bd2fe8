"""Mean seconds per job from the constructor to the scored frame that no
linker stage accounts for: pandas and facade work outside any stage."""


def read(run: dict):
    jobs = run["jobs"]
    if not jobs:
        return None
    return sum(j["scored_s"] - sum(j["scored_stages"].values()) for j in jobs) / len(jobs)
