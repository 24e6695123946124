"""Gateway: jobs a coalesced flush carried — mean `jobs` of the
`gateway.flush` spans.  The clients in flight where they stay in step;
1 where the linger gathers nothing.  Nothing to read where the program
has no such span."""


def read(obs):
    jobs = [s["attrs"]["jobs"] for s in obs.spans if s["name"] == "gateway.flush"]
    return sum(jobs) / len(jobs) if jobs else None
