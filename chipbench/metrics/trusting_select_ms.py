"""Verify surfaces: mean `commit.select` span of the trusting checks
(`mode == "trusting"`): the address-matched walk over the candidate's
commit, a lookup into the TRUSTED set a row, until a third of its power is
matched or the commit ends."""


def read(obs):
    durs = [s["dur_ns"] for s in obs.spans if s["name"] == "commit.select"
            and s["attrs"].get("mode") == "trusting"]
    return sum(durs) / len(durs) / 1e6 if durs else None
