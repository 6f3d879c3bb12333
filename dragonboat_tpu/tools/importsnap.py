"""ImportSnapshot: rebuild a quorum-lost raft group from an exported image.

Reference: ``tools/import.go:130-218`` ``ImportSnapshot``.  Disaster
recovery flow: while the cluster still had quorum somebody exported a
snapshot (``NodeHost.sync_request_snapshot(..., export_path=...)``); after
quorum loss, EVERY surviving/replacement member runs
:func:`import_snapshot` against its own NodeHost dir with the SAME new
membership map and its own node id, then restarts the group normally.
The snapshot's membership is overwritten with the new map, so the
restarted group forms a quorum among exactly those members.

What the import writes (mirroring the reference):
- the snapshot image copied into the NodeHost's snapshot dir layout with
  a rewritten metadata flag file (``imported=True``, membership = new map,
  ``config_change_id = snapshot index``)
- the LogDB bootstrap record for (cluster, node) carrying the new map
- the snapshot record + raft ``State{term, commit=index}`` so replay
  starts from the image
- any pre-existing snapshot records for the node are dropped
"""
from __future__ import annotations

import os
import shutil
from dataclasses import replace
from typing import Dict

from .. import vfs
from ..config import NodeHostConfig
from ..logdb import open_logdb
from ..logger import get_logger
from ..rsm.snapshotio import validate_snapshot_file
from ..server.snapshotenv import (
    SSEnv,
    SSMode,
    read_ss_metadata,
    snapshot_dir_name,
)
from ..wire import Bootstrap, Membership, Snapshot, State, Update

plog = get_logger("tools")


def _host_dir(nhconfig: NodeHostConfig) -> str:
    # must match the ServerContext deployment-id layout the NodeHost uses
    # (server/context.py get_logdb_dirs)
    from ..server.context import ServerContext

    ctx = ServerContext(nhconfig)
    data_dir, _ = ctx.get_logdb_dirs(nhconfig.get_deployment_id())
    return data_dir


def _snapshot_dir(nhconfig: NodeHostConfig, cluster_id: int, node_id: int) -> str:
    # must match NodeHost.snapshot_dir layout (ServerContext)
    from ..server.context import ServerContext

    ctx = ServerContext(nhconfig)
    return ctx.get_snapshot_dir(
        nhconfig.get_deployment_id(), cluster_id, node_id
    )


def import_snapshot(
    nhconfig: NodeHostConfig,
    src_dir: str,
    members: Dict[int, str],
    node_id: int,
) -> Snapshot:
    """Import the exported snapshot in ``src_dir`` for ``node_id``.

    ``members`` is the complete post-repair membership
    ``{node_id: raft_address}``; ``node_id`` must be one of them and its
    address must equal ``nhconfig.raft_address``
    (reference ``tools/import.go:139-166`` validations).
    """
    nhconfig.validate()
    nhconfig.prepare()
    if node_id not in members:
        raise ValueError(f"node {node_id} not in the new membership")
    if members[node_id] != nhconfig.raft_address:
        raise ValueError(
            f"node {node_id} address {members[node_id]!r} != "
            f"NodeHost raft address {nhconfig.raft_address!r}"
        )
    ss = read_ss_metadata(src_dir)
    if ss is None:
        raise ValueError(f"no exported snapshot metadata in {src_dir!r}")
    src_image = os.path.join(src_dir, f"{snapshot_dir_name(ss.index)}.ss")
    if not os.path.exists(src_image):
        raise FileNotFoundError(src_image)
    if not validate_snapshot_file(src_image):
        raise ValueError(f"corrupted snapshot image {src_image!r}")
    for nid in ss.membership.witnesses:
        if nid in members:
            raise ValueError(f"witness {nid} cannot be a voting member")

    cluster_id = ss.cluster_id
    # rewritten record: new membership, imported marker
    # (reference import.go getProcessedSnapshotRecord)
    membership = Membership(
        config_change_id=ss.index,
        addresses=dict(members),
    )
    dst_root = _snapshot_dir(nhconfig, cluster_id, node_id)
    vfs.DEFAULT.makedirs(dst_root, exist_ok=True)
    env = SSEnv(dst_root, ss.index, node_id, SSMode.SNAPSHOT)
    env.remove_final_dir()
    env.create_tmp_dir()
    dst_image = env.get_tmp_filepath()
    shutil.copyfile(src_image, dst_image)
    imported = replace(
        ss,
        filepath=env.get_filepath(),
        file_size=os.path.getsize(dst_image),
        membership=membership,
        imported=True,
        files=list(ss.files),
    )
    # external files travel with the image dir
    for f in ss.files:
        src_f = os.path.join(src_dir, os.path.basename(f.filepath))
        if os.path.exists(src_f):
            shutil.copyfile(
                src_f, os.path.join(env.get_tmp_dir(), os.path.basename(f.filepath))
            )
    env.save_ss_metadata(imported)
    env.finalize_snapshot()

    # the host's own geometry: the shards its directory has, else what its
    # NodeHost would create (a record in another shard is never read)
    db = open_logdb(
        os.path.join(_host_dir(nhconfig), "logdb"),
        **nhconfig.open_logdb_args(),
    )
    try:
        # drop stale snapshot records (reference import.go:200-207)
        for old in db.list_snapshots(cluster_id, node_id):
            db.delete_snapshot(cluster_id, node_id, old.index)
        db.save_bootstrap_info(
            cluster_id, node_id, Bootstrap(addresses=dict(members), join=False)
        )
        db.save_snapshot(cluster_id, node_id, imported)
        db.save_raft_state(
            [
                Update(
                    cluster_id=cluster_id,
                    node_id=node_id,
                    state=State(term=ss.term, vote=0, commit=ss.index),
                )
            ]
        )
    finally:
        db.close()
    plog.info(
        "imported snapshot idx=%d for cluster=%d node=%d, members=%s",
        ss.index,
        cluster_id,
        node_id,
        members,
    )
    return imported
