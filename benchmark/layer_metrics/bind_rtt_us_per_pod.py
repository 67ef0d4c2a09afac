"""client/remote bind_many round trip: the ``remote.request`` spans under
``commit.bind`` over the bindings they carried."""


def bind_requests(facts) -> list:
    return [s for s in facts.get("spans") or []
            if s["name"] == "remote.request" and s["parent"] == "commit.bind"
            and s["attrs"].get("items")]


def read(facts):
    spans = bind_requests(facts)
    items = sum(s["attrs"]["items"] for s in spans)
    return sum(s["dur"] for s in spans) * 1e6 / items if items else None
