"""S3 SigV4 client of the benchmark: a copy of tests/s3util.py's signer
(the yardstick may not change under a later PR), cut to what the cells
send, plus one kept-alive connection per client and a streaming read
that stamps the first and the last body byte.

Deliberately independent of garage_tpu.api.signature, as the original.
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import time
import urllib.parse
import xml.etree.ElementTree as ET
from dataclasses import dataclass
from typing import Optional

ALGORITHM = "AWS4-HMAC-SHA256"


def _sha256(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def uri_encode(s: str, encode_slash: bool = True) -> str:
    return urllib.parse.quote(s, safe="-_.~" if encode_slash else "-_.~/")


@dataclass
class Reply:
    """One exchange as the client saw it. Times are time.monotonic():
    `t_send` just before the first header byte is written, `t_first`
    when the first body byte has been received (the headers, for an
    empty body), `t_last` when the last body byte has."""

    status: int
    headers: dict
    body: bytes
    t_send: float
    t_first: float
    t_last: float


class S3Client:
    """Signs requests and sends each over one kept-alive connection
    (one client per load-generator thread; not thread-safe)."""

    def __init__(self, host: str, port: int, key_id: str, secret: str,
                 region: str = "garage", timeout: float = 900.0):
        self.host, self.port = host, port
        self.key_id, self.secret, self.region = key_id, secret, region
        # long: on a cell's first run in a checkout the first requests
        # wait for XLA to build the programs they launch, minutes of it
        self.timeout = timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    # ---- signing (tests/s3util.py, verbatim in substance) --------------

    def _scope(self, date: str) -> str:
        return f"{date}/{self.region}/s3/aws4_request"

    def signing_key(self, date: str) -> bytes:
        k = _hmac(b"AWS4" + self.secret.encode(), date)
        k = _hmac(k, self.region)
        k = _hmac(k, "s3")
        return _hmac(k, "aws4_request")

    def _canonical_query(self, query: list[tuple[str, str]]) -> str:
        pairs = sorted((uri_encode(k), uri_encode(v)) for k, v in query)
        return "&".join(f"{k}={v}" for k, v in pairs)

    def sign(self, method: str, path: str, query: list[tuple[str, str]],
             headers: dict[str, str], payload_hash: str) -> dict[str, str]:
        """-> headers + Authorization. `headers` must already hold host;
        x-amz-date and x-amz-content-sha256 are added here."""
        now = datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        date = now.strftime("%Y%m%d")
        headers = dict(headers)
        headers["x-amz-date"] = amz_date
        headers["x-amz-content-sha256"] = payload_hash
        signed = sorted(headers)
        canonical_headers = "".join(
            f"{h}:{' '.join(str(headers[h]).split())}\n" for h in signed)
        creq = "\n".join([
            method,
            # S3 convention: the single-encoded request path VERBATIM
            path or "/",
            self._canonical_query(query),
            canonical_headers,
            ";".join(signed),
            payload_hash,
        ])
        sts = "\n".join([ALGORITHM, amz_date, self._scope(date),
                         _sha256(creq.encode())])
        sig = hmac.new(self.signing_key(date), sts.encode(),
                       hashlib.sha256).hexdigest()
        headers["authorization"] = (
            f"{ALGORITHM} Credential={self.key_id}/{self._scope(date)},"
            f"SignedHeaders={';'.join(signed)},Signature={sig}")
        return headers

    # ---- the exchange --------------------------------------------------

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request(self, method: str, path: str,
                query: Optional[list[tuple[str, str]]] = None,
                headers: Optional[dict[str, str]] = None,
                body: bytes = b"", unsigned_payload: bool = False) -> Reply:
        """One signed request on the kept-alive connection. The body is
        read as it arrives, into one buffer of the announced length.
        Raises OSError / http.client.HTTPException when the exchange
        breaks; the connection is then dropped and the next request
        opens a new one."""
        query = query or []
        hdrs = {k.lower(): v for k, v in (headers or {}).items()}
        hdrs["host"] = f"{self.host}:{self.port}"
        payload_hash = ("UNSIGNED-PAYLOAD" if unsigned_payload
                        else _sha256(body))
        hdrs = self.sign(method, path, query, hdrs, payload_hash)
        qs = "&".join(f"{uri_encode(k)}={uri_encode(v)}" for k, v in query)
        url = path + ("?" + qs if qs else "")
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=self.timeout)
            self._conn.connect()
        conn = self._conn
        try:
            t_send = time.monotonic()
            conn.request(method, url, body=body, headers=hdrs)
            r = conn.getresponse()
            rhdrs = {k.lower(): v for k, v in r.getheaders()}
            n = r.length
            if n is None:  # chunked or unknown: no first-byte stamp worth having
                data = r.read()
                t_first = t_last = time.monotonic()
            else:
                buf = bytearray(n)
                view, got = memoryview(buf), 0
                t_first = None
                while got < n:
                    if t_first is None:
                        # read1: what one read of the socket brings, so
                        # that the stamp is the first byte's and not the
                        # full buffer's (readinto fills all it is given)
                        piece = r.read1(min(n, 1 << 16))
                        k = len(piece)
                        view[:k] = piece
                    else:
                        k = r.readinto(view[got:])
                    if k <= 0:
                        raise http.client.IncompleteRead(bytes(buf[:got]),
                                                         n - got)
                    if t_first is None:
                        t_first = time.monotonic()
                    got += k
                t_last = time.monotonic()
                if t_first is None:
                    t_first = t_last
                r.read()  # let http.client see the end of the response
                data = bytes(buf) if n < (1 << 16) else buf
            if r.will_close:
                self.close()
            return Reply(r.status, rhdrs, data, t_send, t_first, t_last)
        except (OSError, http.client.HTTPException):
            self.close()
            raise


def xml_find(body: bytes, tag: str) -> list[str]:
    """All text values of elements whose tag ends with `tag`."""
    out = []
    for el in ET.fromstring(bytes(body)).iter():
        if el.tag.split("}")[-1] == tag:
            out.append(el.text or "")
    return out
