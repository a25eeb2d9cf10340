"""Drives that came back blank: what a configuration's `drives_blank` state
means on the files at rest, from outside the server's process.

A blank drive is online and formatted and holds the bucket, but no object:
under `<root>/<bucket>/` every object's directory (its journal and its
shard files) is gone. The root, the format and the bucket directory stay.
`run.apply_state` brings that about for every object once, after the
preload; a client worker brings it about again for the objects of one group
immediately before it asks for that group's heal (`"again":
"before_each_heal"`), and looks under the same roots after the reply. The
worker does not delete what it takes off a drive: it moves each object's
directory aside, one rename, into a directory of the run that goes with the
run, so that the load generator's own work between two heals stays small
beside a heal.
Imports nothing of the program.
"""

from __future__ import annotations

import glob
import os
import shutil


def blank_roots(drive_roots: list[str], state: dict | None) -> list[str]:
    """The roots a `drives_blank` state names; none for any other state."""
    if not state or "drives_blank" not in state:
        return []
    return drive_roots[:int(state["drives_blank"])]


def blank_drives(roots: list[str], bucket: str) -> None:
    """Remove every object under the bucket of each root; the bucket's own
    directory stays."""
    for root in roots:
        top = os.path.join(root, bucket)
        for name in os.listdir(top):
            shutil.rmtree(os.path.join(top, name))


def blank_objects(roots: list[str], bucket: str, keys: list[str],
                  aside: str, names) -> None:
    """Take these objects' directories off each root, into `aside` (a
    directory on the roots' file system) under the next of `names`; the
    directories above them stay, an object that is not there is left so."""
    for root in roots:
        for key in keys:
            try:
                os.rename(os.path.join(root, bucket, key),
                          os.path.join(aside, next(names)))
            except FileNotFoundError:
                pass


def journals_at_rest(roots: list[str], bucket: str, keys: list[str],
                     journal: str = "meta.mp") -> bool:
    """Every object's journal is a file under every root: the program's
    group commit has written out what it had acknowledged from memory, so
    nothing of these objects is written after they are removed."""
    return all(os.path.isfile(os.path.join(root, bucket, key, journal))
               for root in roots for key in keys)


def shard_files_absent(roots: list[str], bucket: str,
                       keys: list[str]) -> int:
    """How many (root, object) pairs hold no `part.1`: one stat a pair."""
    return sum(not glob.glob(os.path.join(
        glob.escape(os.path.join(root, bucket, key)), "*", "part.1"))
        for root in roots for key in keys)
