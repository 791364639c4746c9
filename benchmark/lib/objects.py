"""The data every cell sends, made from the seed, and the plain
reference it is checked against.

The system is an object store: its plain reference is "the bytes that
went in come out, and the multipart ETag is md5 of the parts' md5s".
This module holds both sides of that: the recipe that makes any part's
bytes from (seed, uploader, sequence number) alone, so the read-back
regenerates what it compares with and trusts nothing the server said,
and the ETag as S3 defines it (hashlib, nothing of garage_tpu).

Blocks are content-addressed, so a block sent twice is the same block
on the same holders and would measure a store that already has it.
Every block of every part sent is therefore made unique: its first
STAMP bytes carry (seed, uploader, sequence number, block index).
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np

STAMP = struct.Struct("<8sqqqq")  # magic, seed, uploader, seq, block index
MAGIC = b"gtpubnch"


class PartPool:
    """A few seeded random parts, made once in set-up; every part sent
    is one of them with its blocks stamped."""

    def __init__(self, seed: int, part_bytes: int, block_bytes: int,
                 n: int = 4):
        if part_bytes < STAMP.size or block_bytes < STAMP.size:
            raise ValueError("part and block must hold the stamp")
        self.seed, self.part_bytes, self.block_bytes = seed, part_bytes, block_bytes
        rng = np.random.default_rng([seed, 0x9A7A])
        self.pool = [rng.integers(0, 256, part_bytes, dtype=np.uint8)
                     for _ in range(n)]

    def part(self, uploader: int, seq: int) -> bytearray:
        """The bytes of part `seq` of `uploader`: pool part
        (uploader + seq) mod n, each block's head stamped."""
        buf = bytearray(self.pool[(uploader + seq) % len(self.pool)].tobytes())
        for bi, off in enumerate(range(0, self.part_bytes, self.block_bytes)):
            if off + STAMP.size > self.part_bytes:
                break
            STAMP.pack_into(buf, off, MAGIC, self.seed, uploader, seq, bi)
        return buf


def multipart_etag(part_md5_hex: list[str]) -> str:
    """S3's multipart ETag: md5 over the parts' binary md5s, "-N"."""
    h = hashlib.md5(b"".join(bytes.fromhex(x) for x in part_md5_hex))
    return f"{h.hexdigest()}-{len(part_md5_hex)}"


def object_sha256(pool: PartPool, uploader: int, seqs: list[int]) -> tuple[str, int]:
    """(sha256 hex, size) of the object made of these parts, regenerated
    from the recipe."""
    h, size = hashlib.sha256(), 0
    for s in seqs:
        p = pool.part(uploader, s)
        h.update(p)
        size += len(p)
    return h.hexdigest(), size


def upload_object(client, key: str, pool: PartPool, uploader: int,
                  seqs: list[int], record, should_stop=lambda: False,
                  want_sha: bool = False):
    """CreateMultipartUpload, one UploadPart per sequence number
    (UNSIGNED-PAYLOAD, as SDKs send behind a TLS proxy), then
    CompleteMultipartUpload. Each part is made and its md5 taken before
    its request is timed. `record(kind, reply_or_exc, nbytes, ok)` sees
    every exchange. -> {"key", "uploader", "seqs", "sha256s"} for a
    completed object (the parts' digests only with `want_sha`: they
    cost the uploader time between requests), None for one abandoned
    or failed."""
    from .s3client import xml_find

    def call(kind, nbytes, ok_of, **kw):
        try:
            r = client.request(**kw)
        except Exception as e:  # counted as a failed request, not raised
            record(kind, e, nbytes, False)
            return None
        ok = r.status == 200 and ok_of(r)
        record(kind, r, nbytes, ok)
        return r if ok else None

    r = call("create", 0, lambda r: True, method="POST", path=key,
             query=[("uploads", "")])
    if r is None:
        return None
    upload_id = xml_find(r.body, "UploadId")[0]
    md5s, shas = [], []
    for pn, seq in enumerate(seqs, start=1):
        if should_stop():
            return None
        part = pool.part(uploader, seq)
        md5 = hashlib.md5(part).hexdigest()
        if want_sha:
            shas.append(hashlib.sha256(part).hexdigest())
        r = call("part", len(part),
                 lambda r, md5=md5: r.headers.get("etag", "").strip('"') == md5,
                 method="PUT", path=key, body=part, unsigned_payload=True,
                 query=[("partNumber", str(pn)), ("uploadId", upload_id)])
        if r is None:
            return None
        md5s.append(md5)
    xml = "".join(f"<Part><PartNumber>{i}</PartNumber><ETag>\"{m}\"</ETag></Part>"
                  for i, m in enumerate(md5s, start=1))
    want = multipart_etag(md5s)
    r = call("complete", 0,
             lambda r: xml_find(r.body, "ETag")[0].strip('"') == want,
             method="POST", path=key, query=[("uploadId", upload_id)],
             body=f"<CompleteMultipartUpload>{xml}</CompleteMultipartUpload>"
             .encode())
    if r is None:
        return None
    return {"key": key, "uploader": uploader, "seqs": list(seqs),
            "sha256s": shas}


def read_back(client, obj: dict, pool: PartPool, record) -> bool:
    """GET the whole object and compare it, part by part, with the
    bytes regenerated from the recipe."""
    try:
        r = client.request("GET", obj["key"])
    except Exception as e:
        record("readback", e, 0, False)
        return False
    pb = pool.part_bytes
    ok = r.status == 200 and len(r.body) == pb * len(obj["seqs"])
    if ok:
        view = memoryview(r.body)
        for i, seq in enumerate(obj["seqs"]):
            ok = ok and view[i * pb:(i + 1) * pb] == pool.part(
                obj["uploader"], seq)
    record("readback", r, len(r.body), ok)
    return ok
