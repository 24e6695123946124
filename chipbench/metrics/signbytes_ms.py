"""Verify surfaces: mean `commit.sign_bytes` span — the one (native) call
that assembles the canonical sign-bytes of a commit's selected rows."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "commit.sign_bytes"]
    return sum(durs) / len(durs) / 1e6 if durs else None
