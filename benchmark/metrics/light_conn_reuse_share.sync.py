"""The share of the light provider's requests in the window that went
out on a connection it already held: 1 - count(light.connect) /
count(light.rpc), the spans light/provider.HttpProvider opens round each
GET and round each connection it opens.  Near 1 where every thread keeps
its connection, 0 where each request opens one; None where no request
went over HTTP (a memory provider) or the program opens no such span."""


def read(run):
    rpc = run.spans.get("light.rpc")
    if rpc is None or not rpc["count"]:
        return None
    connect = run.spans.get("light.connect")
    return 1.0 - (connect["count"] if connect else 0) / rpc["count"]
