"""ObjectLayer contract tests, parameterized over backends.

The reference's tier-2 pattern (ExecObjectLayerTest,
cmd/test-utils_test.go:1892): one test body runs against FS and erasure
backends so every ObjectLayer implementation honors the same contract.
"""

import io
import os

import pytest

from minio_tpu.erasure.objects import ErasureObjects
from minio_tpu.erasure.pools import ErasureServerPools
from minio_tpu.erasure.sets import ErasureSets
from minio_tpu.erasure.types import CompletePart, ObjectOptions, ObjectToDelete
from minio_tpu.fs import FSObjects
from minio_tpu.layer import ObjectLayer
from minio_tpu.storage.local import LocalDrive
from minio_tpu.utils import errors as se

# "erasure4-mxsum256" is the configuration the chip serves: the device
# checksum sends PUT, GET and heal through dataplane/route.py and the
# fused launches. Without an algorithm a CPU backend defaults to sip256,
# whose objects ride the C++ host lane.
BACKENDS = ["fs", "erasure4", "erasure4-mxsum256", "erasure-sets8"]


@pytest.fixture(params=BACKENDS)
def layer(request, tmp_path):
    """The ExecObjectLayerTest fixture: same body, every backend."""
    kind = request.param
    if kind == "fs":
        obj = FSObjects(str(tmp_path / "fsroot"))
    elif kind == "erasure4":
        drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)]
        obj = ErasureObjects(drives, parity=2)
    elif kind == "erasure4-mxsum256":
        drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(4)]
        obj = ErasureObjects(drives, parity=2, bitrot_algorithm="mxsum256")
    else:
        drives = [LocalDrive(str(tmp_path / f"d{i}")) for i in range(8)]
        obj = ErasureServerPools([ErasureSets(drives, set_drive_count=4)])
    assert isinstance(obj, ObjectLayer)
    yield obj
    obj.close()


def test_bucket_lifecycle(layer):
    layer.make_bucket("contract")
    assert layer.get_bucket_info("contract").name == "contract"
    assert "contract" in [b.name for b in layer.list_buckets()]
    with pytest.raises(se.BucketExists):
        layer.make_bucket("contract")
    with pytest.raises(se.BucketNameInvalid):
        layer.make_bucket("UPPER")
    with pytest.raises(se.BucketNameInvalid):
        layer.make_bucket("ab")
    layer.delete_bucket("contract")
    with pytest.raises(se.BucketNotFound):
        layer.get_bucket_info("contract")
    with pytest.raises(se.BucketNotFound):
        layer.delete_bucket("contract")


def test_object_roundtrip_and_errors(layer):
    layer.make_bucket("bkt")
    with pytest.raises(se.BucketNotFound):
        layer.put_object("missing", "o", io.BytesIO(b"x"), 1)

    payload = os.urandom(100_000)
    info = layer.put_object("bkt", "dir/obj", io.BytesIO(payload),
                            len(payload))
    assert info.size == len(payload)
    assert info.etag

    got = layer.get_object_info("bkt", "dir/obj")
    assert got.size == len(payload) and got.etag == info.etag

    _, it = layer.get_object("bkt", "dir/obj")
    assert b"".join(it) == payload
    _, it = layer.get_object("bkt", "dir/obj", offset=1000, length=500)
    assert b"".join(it) == payload[1000:1500]
    with pytest.raises(se.InvalidRange):
        _, it = layer.get_object("bkt", "dir/obj", offset=len(payload) + 1,
                                 length=10)
        b"".join(it)

    with pytest.raises(se.ObjectNotFound):
        layer.get_object_info("bkt", "nope")

    layer.delete_object("bkt", "dir/obj")
    with pytest.raises(se.ObjectNotFound):
        layer.get_object_info("bkt", "dir/obj")


def test_overwrite_replaces(layer):
    layer.make_bucket("bkt")
    layer.put_object("bkt", "o", io.BytesIO(b"first"), 5)
    layer.put_object("bkt", "o", io.BytesIO(b"second!"), 7)
    info = layer.get_object_info("bkt", "o")
    assert info.size == 7
    _, it = layer.get_object("bkt", "o")
    assert b"".join(it) == b"second!"


def test_incomplete_body_rejected(layer):
    layer.make_bucket("bkt")
    with pytest.raises(se.IncompleteBody):
        layer.put_object("bkt", "o", io.BytesIO(b"short"), 100)
    with pytest.raises(se.ObjectNotFound):
        layer.get_object_info("bkt", "o")


def test_listing_pagination_and_delimiters(layer):
    layer.make_bucket("bkt")
    for name in ["a/1", "a/2", "b/1", "top1", "top2"]:
        layer.put_object("bkt", name, io.BytesIO(b"x"), 1)

    res = layer.list_objects("bkt")
    assert [o.name for o in res.objects] == ["a/1", "a/2", "b/1",
                                             "top1", "top2"]
    res = layer.list_objects("bkt", delimiter="/")
    assert [o.name for o in res.objects] == ["top1", "top2"]
    assert res.prefixes == ["a/", "b/"]
    res = layer.list_objects("bkt", prefix="a/")
    assert [o.name for o in res.objects] == ["a/1", "a/2"]
    res = layer.list_objects("bkt", max_keys=2)
    assert len(res.objects) == 2 and res.is_truncated
    res2 = layer.list_objects("bkt", marker=res.next_marker)
    assert [o.name for o in res2.objects] == ["b/1", "top1", "top2"]


def test_bulk_delete(layer):
    layer.make_bucket("bkt")
    for name in ["x", "y"]:
        layer.put_object("bkt", name, io.BytesIO(b"d"), 1)
    results = layer.delete_objects(
        "bkt", [ObjectToDelete("x"), ObjectToDelete("y"),
                ObjectToDelete("ghost")])
    assert not isinstance(results[0], Exception)
    assert not isinstance(results[1], Exception)
    assert isinstance(results[2], Exception)


def test_tags_roundtrip(layer):
    layer.make_bucket("bkt")
    layer.put_object("bkt", "o", io.BytesIO(b"x"), 1)
    layer.put_object_tags("bkt", "o", "k1=v1&k2=v2")
    assert layer.get_object_tags("bkt", "o") == "k1=v1&k2=v2"
    layer.delete_object_tags("bkt", "o")
    assert layer.get_object_tags("bkt", "o") == ""


def test_multipart_contract(layer):
    layer.make_bucket("bkt")
    uid = layer.new_multipart_upload("bkt", "big")
    assert any(u.upload_id == uid for u in layer.list_multipart_uploads("bkt"))

    part1 = os.urandom(5 << 20)
    part2 = os.urandom(1 << 20)
    p1 = layer.put_object_part("bkt", "big", uid, 1, io.BytesIO(part1),
                               len(part1))
    p2 = layer.put_object_part("bkt", "big", uid, 2, io.BytesIO(part2),
                               len(part2))
    listed = layer.list_parts("bkt", "big", uid)
    assert [p.part_number for p in listed] == [1, 2]

    with pytest.raises(se.InvalidPart):
        layer.complete_multipart_upload(
            "bkt", "big", uid, [CompletePart(1, "wrong-etag")])

    info = layer.complete_multipart_upload(
        "bkt", "big", uid,
        [CompletePart(1, p1.etag), CompletePart(2, p2.etag)])
    assert info.size == len(part1) + len(part2)
    assert info.etag.endswith("-2")
    _, it = layer.get_object("bkt", "big")
    assert b"".join(it) == part1 + part2
    # Session gone after completion.
    with pytest.raises(se.InvalidUploadID):
        layer.list_parts("bkt", "big", uid)


def test_multipart_abort(layer):
    layer.make_bucket("bkt")
    uid = layer.new_multipart_upload("bkt", "gone")
    layer.put_object_part("bkt", "gone", uid, 1, io.BytesIO(b"data"), 4)
    layer.abort_multipart_upload("bkt", "gone", uid)
    with pytest.raises(se.InvalidUploadID):
        layer.list_parts("bkt", "gone", uid)
    with pytest.raises(se.ObjectNotFound):
        layer.get_object_info("bkt", "gone")


def test_sys_config_store_contract(layer):
    layer.write_sys_config("contract/test.bin", b"payload")
    assert layer.read_sys_config("contract/test.bin") == b"payload"
    assert "contract/test.bin" in layer.list_sys_config("contract")
    layer.delete_sys_config("contract/test.bin")
    with pytest.raises(se.FileNotFound):
        layer.read_sys_config("contract/test.bin")


def test_put_object_metadata_contract(layer):
    layer.make_bucket("bkt")
    layer.put_object("bkt", "o", io.BytesIO(b"x"), 1)
    layer.put_object_metadata("bkt", "o", {"x-custom": "v"})
    assert layer.get_object_info("bkt", "o").user_defined["x-custom"] == "v"
    layer.put_object_metadata("bkt", "o", {"x-custom": None})
    assert "x-custom" not in layer.get_object_info("bkt", "o").user_defined


def test_health_and_heal_shape(layer):
    h = layer.health()
    assert h["healthy"] is True
    layer.make_bucket("bkt")
    layer.put_object("bkt", "o", io.BytesIO(b"x"), 1)
    item = layer.heal_bucket("bkt")
    assert item.bucket == "bkt"
    item = layer.heal_object("bkt", "o")
    assert item.object in ("o", "")
