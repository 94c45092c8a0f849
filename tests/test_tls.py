"""TLS serving + skip-verify internal client.

Reference: server/config.go (tls.certificate, tls.key, tls.skip-verify) —
upstream serves HTTPS when a cert/key pair is configured and lets the
node→node client trust self-signed certs. Certs here are generated
per-session with the system openssl (self-signed, localhost SAN):
conftest.py's ``certpair``.
"""

import json
import ssl
import urllib.request

import pytest

from pilosa_tpu.parallel.client import InternalClient
from pilosa_tpu.server import Server
from pilosa_tpu.utils.config import Config, load_config


@pytest.fixture
def tls_srv(tmp_path, certpair):
    cert, key = certpair
    s = Server(
        Config(
            bind="127.0.0.1:0",
            data_dir=str(tmp_path / "data"),
            anti_entropy_interval=0,
            tls_certificate=cert,
            tls_key=key,
        )
    )
    s.open()
    yield s
    s.close()


def _https_call(srv, method, path, body=None, verify_cert=None):
    ctx = ssl.create_default_context(cafile=verify_cert)
    if verify_cert is None:
        ctx = ssl._create_unverified_context()
    data = (
        body
        if isinstance(body, (bytes, type(None)))
        else json.dumps(body).encode()
    )
    req = urllib.request.Request(srv.uri + path, data=data, method=method)
    with urllib.request.urlopen(req, context=ctx) as resp:
        return json.loads(resp.read() or b"{}")


def test_https_query_roundtrip(tls_srv, certpair):
    assert tls_srv.uri.startswith("https://")
    # full workflow over TLS, verifying against the self-signed CA cert
    cert, _ = certpair
    assert _https_call(tls_srv, "POST", "/index/i", {}, verify_cert=cert)["success"]
    assert _https_call(tls_srv, "POST", "/index/i/field/f", {}, verify_cert=cert)[
        "success"
    ]
    r = _https_call(tls_srv, "POST", "/index/i/query", b"Set(1, f=1) Set(3, f=1)")
    assert r["results"] == [True, True]
    r = _https_call(tls_srv, "POST", "/index/i/query", b"Count(Row(f=1))")
    assert r["results"] == [2]


def test_plain_http_rejected_by_tls_server(tls_srv):
    # a plaintext client speaking HTTP to the TLS port must fail, not hang
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{tls_srv.port}/status", timeout=5
        )


def test_internal_client_skip_verify(tls_srv):
    # the node→node client path upstream uses with tls.skip-verify
    c = InternalClient(skip_verify=True)
    st = c.status(tls_srv.uri)
    assert st["state"] in ("NORMAL", "STARTING")
    # without skip_verify the self-signed cert must be rejected
    strict = InternalClient()
    with pytest.raises(Exception):
        strict.status(tls_srv.uri, timeout=5)


def test_tls_config_keys_load(tmp_path):
    p = tmp_path / "c.toml"
    p.write_text(
        'tls-certificate = "/tmp/x.crt"\ntls-key = "/tmp/x.key"\n'
        "tls-skip-verify = true\n"
    )
    cfg = load_config(str(p))
    assert cfg.tls_certificate == "/tmp/x.crt"
    assert cfg.tls_key == "/tmp/x.key"
    assert cfg.tls_skip_verify is True
    assert cfg.scheme == "https"
    assert cfg.uri.startswith("https://")
    # env layer
    cfg = load_config(None, env={"PILOSA_TPU_TLS_SKIP_VERIFY": "1"})
    assert cfg.tls_skip_verify is True
    assert Config().scheme == "http"
