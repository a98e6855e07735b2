"""Self time of the TCP front per request, ms: each ``tcp:request`` span
of the program less its ``tcp:await`` on the same thread (JSON decoding
and encoding, the request's construction, the reply's write), averaged
over the requests that start in the device-traced window
(``spans.py``)."""

import spans


def read(rec):
    return spans.front_ms(spans.load(rec))
