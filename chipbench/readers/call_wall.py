"""Mean seconds per job inside one facade call (host clock around it)."""


def read(run: dict, call: str):
    walls = [j["call_s"][call] for j in run["jobs"] if call in j["call_s"]]
    return sum(walls) / len(walls) if walls else None
