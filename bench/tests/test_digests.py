"""Traffic without ``writes`` sends what it sent before the load
generator took writes: the encoded requests and their due times of both
cells' traffic, at their configurations' sizes and a 51 s window, hash
to the digests the generator gave before."""

import hashlib

import pytest

import client
import harness

SEED = 2**31 + 15
DIGESTS = {
    "hybrid-steady": (
        "msmarco-hybrid", 1469,
        "fe2b7bbfb971525afc03e037faf4777c5d5f96001a88fbb166d64256987e538f",
        "75d1ad9f1f274879106eaa63f0a57673454061cbc6b85f8a002c41438d61d059"),
    "plaid-steady": (
        "msmarco-plaid", 408,
        "eda6ba85bcc0a5e8e2ff96a0164993c2ad40f70fe435da09e9eccac7a312e965",
        "f2344e9d1e0bf8c2ef704a93fe917ffcc54ddaeace2ebb9e2f18d5812f346365"),
}


@pytest.mark.parametrize("traffic", sorted(DIGESTS))
def test_requests_and_due_times_are_unchanged(traffic):
    config, n, due_digest, req_digest = DIGESTS[traffic]
    cfg = harness.load_json(harness.HERE / "configs" / f"{config}.json")
    t = harness.load_json(harness.HERE / "traffic" / f"{traffic}.json")
    due, w_due = client.split(t, client.schedule(t, 51.0, SEED), SEED)
    assert len(due) == n and len(w_due) == 0
    assert hashlib.sha256(due.tobytes()).hexdigest() == due_digest
    reqs = client.encode_requests(cfg, t, SEED, n)
    assert hashlib.sha256(b"".join(reqs)).hexdigest() == req_digest
