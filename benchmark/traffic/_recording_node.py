"""The stand-in for the chain on the upload path.

OssGateway.upload makes one chain call,
``node.submit_extrinsic(account, "file_bank.upload_declaration", file_hash,
seg_list, UserBrief, size)`` (cess_tpu/node/offchain.py). No cell runs a
chain, validators or miners (cess-protocol.json, ``reduced``): this object
records each call's arguments, and the check reads them back.
"""
from __future__ import annotations


class RecordingNode:
    def __init__(self):
        self.extrinsics: list[tuple] = []

    def submit_extrinsic(self, account: str, call: str, *args) -> None:
        self.extrinsics.append((account, call, args))
