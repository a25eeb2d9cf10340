"""Error classes of the storage stack.

Mirrors the reference's typed storage errors (cmd/typed-errors.go,
cmd/storage-errors.go) as an exception hierarchy. Quorum logic reduces lists
of these per-drive errors into a single outcome (see utils/quorum.py;
reference cmd/erasure-metadata-utils.go:72-100).
"""

from __future__ import annotations


class StorageError(Exception):
    """Base for all per-drive storage errors."""


class DiskNotFound(StorageError):
    """Drive is offline / not reachable."""


class FaultyDisk(StorageError):
    """Drive returned an unexpected I/O error."""


class DiskFull(StorageError):
    pass


class DiskAccessDenied(StorageError):
    pass


class UnformattedDisk(StorageError):
    """Drive has no format.json yet."""


class InconsistentDisk(StorageError):
    """Drive's format.json identity does not match the expected drive
    (detects swapped/replugged disks — reference cmd/xl-storage-disk-id-check.go:64)."""


class VolumeNotFound(StorageError):
    pass


class VolumeExists(StorageError):
    pass


class VolumeNotEmpty(StorageError):
    pass


class FileNotFound(StorageError):
    pass


class FileVersionNotFound(StorageError):
    pass


class FileNameTooLong(StorageError):
    pass


class FileAccessDenied(StorageError):
    pass


class FileCorrupt(StorageError):
    """Bitrot verification failed on read (reference errFileCorrupt,
    cmd/bitrot-streaming.go:139-158)."""


class IsNotRegular(StorageError):
    """Path exists but is a directory where a file was expected (or vice versa)."""


class CorruptedFormat(StorageError):
    pass


class MethodNotAllowed(StorageError):
    pass


# --- object-layer errors (reference cmd/object-api-errors.go) ---


class ObjectError(Exception):
    def __init__(self, bucket: str = "", object: str = "", msg: str = ""):
        self.bucket = bucket
        self.object = object
        super().__init__(msg or f"{type(self).__name__}: {bucket}/{object}")


class BucketNotFound(ObjectError):
    pass


class BucketExists(ObjectError):
    pass


class BucketNotEmpty(ObjectError):
    pass


class BucketNameInvalid(ObjectError):
    pass


class ObjectNotFound(ObjectError):
    pass


class VersionNotFound(ObjectError):
    pass


class ObjectNameInvalid(ObjectError):
    pass


class ObjectExistsAsDirectory(ObjectError):
    pass


class InvalidUploadID(ObjectError):
    pass


class InvalidPart(ObjectError):
    pass


class PartTooSmall(ObjectError):
    pass


class IncompleteBody(ObjectError):
    pass


class InsufficientReadQuorum(ObjectError):
    """Fewer than dataBlocks drives agreed on a readable object."""


class InsufficientWriteQuorum(ObjectError):
    """Fewer than writeQuorum drives accepted the write."""


class PreconditionFailed(ObjectError):
    pass


class InvalidRange(ObjectError):
    pass


class OperationTimedOut(ObjectError):
    pass


class AdmissionShed(OperationTimedOut):
    """A batch-plane admission rejection (utils/admission.shed): the
    request was shed by policy — queue share, tenant quota, or plane
    shutdown — not lost to a sick drive. Subclassing OperationTimedOut
    keeps the S3 mapping (503 SlowDown) and every existing isinstance
    site, while letting the drive-health layer exclude sheds from its
    failure accounting: backpressure must never walk a drive OFFLINE."""


# --- IAM / policy errors (reference cmd/iam-errors.go, pkg/iam/policy) ---


class IAMError(Exception):
    pass


class MalformedPolicy(IAMError):
    pass


class NoSuchPolicy(IAMError):
    pass


class NoSuchUser(IAMError):
    pass


class NoSuchGroup(IAMError):
    pass


class NoSuchServiceAccount(IAMError):
    pass


class InvalidAccessKey(IAMError):
    pass


class IAMActionNotAllowed(IAMError):
    pass


# --- wire transport helpers (dist/rpc.py) -----------------------------------
#
# Storage RPC carries errors by class name; the client re-raises the same
# typed exception so quorum reducers behave identically for local and remote
# drives (the reference ships error *strings* over storage REST and converts
# back with toStorageErr, cmd/storage-rest-client.go:113-160).

def by_name(name: str, msg: str = "") -> Exception:
    """Rebuild a typed storage/object error from its class name."""
    cls = globals().get(name)
    if isinstance(cls, type) and issubclass(cls, ObjectError):
        return cls(msg=msg)
    if isinstance(cls, type) and issubclass(cls, StorageError):
        return cls(msg)
    return StorageError(f"{name}: {msg}")
