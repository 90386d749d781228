"""HTTP/SSE front end: p90 of the server's ``sse.write`` spans that end
inside the window. Each runs from its token reaching the host (the end of
the wait that pulled it from the device) to the drain of the socket
after the token's frame: the time the token sat between the tick thread
and the client's connection. Moves tpot_p90_s."""

from _window import p90


def read(run):
    return p90(sp.end - sp.start for sp in run.spans
               if sp.name == "sse.write" and sp.end is not None
               and run.t_open <= sp.end <= run.t_close)
