"""Independent S3 SigV4 client for conformance tests.

Deliberately does NOT reuse garage_tpu.api.signature — this is a
from-scratch signer over http.client so server-side verification is
exercised against a second implementation (the reference does the same
with aws-sdk-s3 + a hand-rolled custom_requester, ref:
src/garage/tests/common/custom_requester.rs).
"""

from __future__ import annotations

import datetime
import hashlib
import hmac
import http.client
import urllib.parse
import xml.etree.ElementTree as ET
from typing import Optional

ALGORITHM = "AWS4-HMAC-SHA256"
EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()


def _sha256(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def uri_encode(s: str, encode_slash: bool = True) -> str:
    return urllib.parse.quote(s, safe="-_.~" if encode_slash else "-_.~/")


class S3Client:
    def __init__(self, host: str, port: int, key_id: str, secret: str,
                 region: str = "garage"):
        self.host = host
        self.port = port
        self.key_id = key_id
        self.secret = secret
        self.region = region

    # ---- signing -------------------------------------------------------

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
             headers: dict[str, str], payload_hash: str,
             now: Optional[datetime.datetime] = None) -> dict[str, str]:
        """-> headers + Authorization. `headers` must already contain
        host; x-amz-date/x-amz-content-sha256 are added here."""
        now = now or datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        date = now.strftime("%Y%m%d")
        headers = dict(headers)
        headers["x-amz-date"] = amz_date
        headers["x-amz-content-sha256"] = payload_hash
        signed = sorted(h.lower() for h in headers)
        canonical_headers = "".join(
            f"{h}:{' '.join(str(headers[next(k for k in headers if k.lower() == h)]).split())}\n"
            for h in signed)
        creq = "\n".join([
            method,
            # S3 convention: the request path is single-encoded by
            # the caller and used VERBATIM as the canonical URI (no
            # re-encoding - %20 must not become %2520)
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

    # ---- plain requests ------------------------------------------------

    def request(self, method: str, path: str,
                query: Optional[list[tuple[str, str]]] = None,
                headers: Optional[dict[str, str]] = None,
                body: bytes = b"", unsigned_payload: bool = False,
                anonymous: bool = False, timeout: float = 30.0):
        """-> (status, headers dict, body bytes)."""
        query = query or []
        headers = {k.lower(): v for k, v in (headers or {}).items()}
        headers.setdefault("host", f"{self.host}:{self.port}")
        if not anonymous:
            payload_hash = ("UNSIGNED-PAYLOAD" if unsigned_payload
                            else _sha256(body))
            headers = self.sign(method, path, query, headers, payload_hash)
        qs = "&".join(f"{uri_encode(k)}={uri_encode(v)}" for k, v in query)
        url = path + ("?" + qs if qs else "")
        conn = http.client.HTTPConnection(self.host, self.port, timeout=timeout)
        try:
            conn.request(method, url, body=body, headers=headers)
            r = conn.getresponse()
            rbody = r.read()
            rhdrs = {k.lower(): v for k, v in r.getheaders()}
            return r.status, rhdrs, rbody
        finally:
            conn.close()

    # ---- aws-chunked streaming bodies ----------------------------------

    def chunked_signed_body(self, chunks: list[bytes], amz_date: str,
                            seed_signature: str,
                            trailer: Optional[tuple[str, str]] = None,
                            sign_trailer_label: str = "AWS4-HMAC-SHA256-TRAILER",
                            ) -> bytes:
        """Build a STREAMING-AWS4-HMAC-SHA256-PAYLOAD[-TRAILER] body."""
        date = amz_date[:8]
        sk = self.signing_key(date)
        prev = seed_signature
        out = bytearray()
        for data in list(chunks) + [b""]:
            sts = "\n".join(["AWS4-HMAC-SHA256-PAYLOAD", amz_date,
                             self._scope(date), prev, EMPTY_SHA256,
                             _sha256(data)])
            sig = hmac.new(sk, sts.encode(), hashlib.sha256).hexdigest()
            out += f"{len(data):x};chunk-signature={sig}\r\n".encode()
            if data:
                out += data + b"\r\n"
            prev = sig
        if trailer is None:
            out += b"\r\n"
        else:
            name, value = trailer
            out += f"{name}:{value}\r\n".encode()
            sts = "\n".join([sign_trailer_label, amz_date, self._scope(date),
                             prev, _sha256(f"{name}:{value}\n".encode())])
            sig = hmac.new(sk, sts.encode(), hashlib.sha256).hexdigest()
            out += f"x-amz-trailer-signature:{sig}\r\n".encode()
            out += b"\r\n"
        return bytes(out)

    def put_chunked(self, path: str, chunks: list[bytes],
                    trailer: Optional[tuple[str, str]] = None,
                    corrupt_chunk_sig: bool = False,
                    extra_headers: Optional[dict[str, str]] = None,
                    query: Optional[list[tuple[str, str]]] = None,
                    timeout: float = 30.0):
        """PUT with aws-chunked signed framing (+ optional signed
        trailer)."""
        mode = ("STREAMING-AWS4-HMAC-SHA256-PAYLOAD-TRAILER" if trailer
                else "STREAMING-AWS4-HMAC-SHA256-PAYLOAD")
        decoded_len = sum(len(c) for c in chunks)
        now = datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        headers = {"host": f"{self.host}:{self.port}",
                   "content-encoding": "aws-chunked",
                   "x-amz-decoded-content-length": str(decoded_len)}
        if trailer:
            headers["x-amz-trailer"] = trailer[0]
        if extra_headers:
            headers.update(extra_headers)
        headers = self.sign("PUT", path, query or [], headers, mode,
                            now=now)
        seed = headers["authorization"].rsplit("Signature=", 1)[1]
        body = self.chunked_signed_body(chunks, amz_date, seed,
                                        trailer=trailer)
        if corrupt_chunk_sig:
            i = body.index(b"chunk-signature=") + len(b"chunk-signature=")
            body = (body[:i]
                    + (b"0" if body[i:i + 1] != b"0" else b"1")
                    + body[i + 1:])
        url = path
        if query:
            url += "?" + "&".join(
                f"{uri_encode(k)}={uri_encode(v)}" for k, v in query)
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=timeout)
        try:
            conn.request("PUT", url, body=body, headers=headers)
            r = conn.getresponse()
            rbody = r.read()
            return r.status, {k.lower(): v for k, v in r.getheaders()}, rbody
        finally:
            conn.close()

    def put_unsigned_trailer(self, path: str, chunks: list[bytes],
                             trailer: tuple[str, str]):
        """PUT with STREAMING-UNSIGNED-PAYLOAD-TRAILER framing."""
        decoded_len = sum(len(c) for c in chunks)
        headers = {"host": f"{self.host}:{self.port}",
                   "content-encoding": "aws-chunked",
                   "x-amz-trailer": trailer[0],
                   "x-amz-decoded-content-length": str(decoded_len)}
        headers = self.sign("PUT", path, [], headers,
                            "STREAMING-UNSIGNED-PAYLOAD-TRAILER")
        out = bytearray()
        for data in list(chunks) + [b""]:
            out += f"{len(data):x}\r\n".encode()
            if data:
                out += data + b"\r\n"
        out += f"{trailer[0]}:{trailer[1]}\r\n".encode()
        out += b"\r\n"
        conn = http.client.HTTPConnection(self.host, self.port, timeout=30)
        try:
            conn.request("PUT", path, body=bytes(out), headers=headers)
            r = conn.getresponse()
            rbody = r.read()
            return r.status, {k.lower(): v for k, v in r.getheaders()}, rbody
        finally:
            conn.close()

    # ---- presigned -----------------------------------------------------

    def presign(self, method: str, path: str, expires: int = 300,
                query: Optional[list[tuple[str, str]]] = None) -> str:
        now = datetime.datetime.now(datetime.timezone.utc)
        amz_date = now.strftime("%Y%m%dT%H%M%SZ")
        date = now.strftime("%Y%m%d")
        q = list(query or []) + [
            ("X-Amz-Algorithm", ALGORITHM),
            ("X-Amz-Credential", f"{self.key_id}/{self._scope(date)}"),
            ("X-Amz-Date", amz_date),
            ("X-Amz-Expires", str(expires)),
            ("X-Amz-SignedHeaders", "host"),
        ]
        creq = "\n".join([
            method,
            # S3 convention: the request path is single-encoded by
            # the caller and used VERBATIM as the canonical URI (no
            # re-encoding - %20 must not become %2520)
            path or "/",
            self._canonical_query(q),
            f"host:{self.host}:{self.port}\n",
            "host",
            "UNSIGNED-PAYLOAD",
        ])
        sts = "\n".join([ALGORITHM, amz_date, self._scope(date),
                         _sha256(creq.encode())])
        sig = hmac.new(self.signing_key(date), sts.encode(),
                       hashlib.sha256).hexdigest()
        q.append(("X-Amz-Signature", sig))
        qs = "&".join(f"{uri_encode(k)}={uri_encode(v)}" for k, v in q)
        return f"{path}?{qs}"

    def raw(self, method: str, url: str, headers: Optional[dict] = None,
            body: bytes = b""):
        """Unsigned raw request (for presigned URLs / anonymous)."""
        headers = headers or {}
        headers.setdefault("host", f"{self.host}:{self.port}")
        conn = http.client.HTTPConnection(self.host, self.port, timeout=30)
        try:
            conn.request(method, url, body=body, headers=headers)
            r = conn.getresponse()
            rbody = r.read()
            return r.status, {k.lower(): v for k, v in r.getheaders()}, rbody
        finally:
            conn.close()


def xml_find(body: bytes, tag: str) -> list[str]:
    """All text values of elements whose tag ends with `tag`."""
    root = ET.fromstring(body)
    out = []
    for el in root.iter():
        if el.tag.split("}")[-1] == tag:
            out.append(el.text or "")
    return out


def xml_error_code(body: bytes) -> str:
    try:
        return xml_find(body, "Code")[0]
    except (ET.ParseError, IndexError):
        return ""
