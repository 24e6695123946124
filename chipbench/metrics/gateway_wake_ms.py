"""Gateway: from the end of a flush's verify call to the moment a client
of it has its answer — end of a `gateway.flush` span to the end of each
`gateway.wait` span with the same `flush` number (never under 0): the
futures set one by one, and the clients woken together under one
interpreter lock.  Mean over the waits paired."""


def read(obs):
    done = {s["attrs"]["flush"]: s["t0_ns"] + s["dur_ns"]
            for s in obs.spans if s["name"] == "gateway.flush"}
    tails = [max(0, s["t0_ns"] + s["dur_ns"] - done[s["attrs"]["flush"]])
             for s in obs.spans
             if s["name"] == "gateway.wait" and s["attrs"].get("flush") in done]
    return sum(tails) / len(tails) / 1e6 if tails else None
