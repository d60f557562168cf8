"""Utility layer: configs, serialization, messages, logging, profiling."""

from distriflow_tpu.utils.compile_cache import enable_compile_cache
from distriflow_tpu.utils.config import (
    ClientHyperparams,
    CompileConfig,
    DatasetConfig,
    MeshConfig,
    RetryPolicy,
    ServerHyperparams,
    ServingConfig,
    UnknownConfigKeyError,
    asdict,
    client_hyperparams,
    dataset_config,
    make_config,
    override,
    server_hyperparams,
)
from distriflow_tpu.utils.logging import CallbackRegistry, VerboseLogger
from distriflow_tpu.utils.messages import (
    DataMsg,
    DownloadMsg,
    Events,
    GradientMsg,
    ModelMsg,
    UploadMsg,
)
from distriflow_tpu.utils.serialization import (
    SerializedArray,
    deserialize_array,
    deserialize_tree,
    flat_deserialize,
    flat_serialize,
    mean_serialized,
    pack_bytes,
    serialize_array,
    serialize_tree,
    stack_serialized,
    tree_from_bytes,
    tree_to_bytes,
    unpack_bytes,
)

__all__ = [
    # compile cache placement
    "enable_compile_cache",
    # config
    "ClientHyperparams",
    "CompileConfig",
    "DatasetConfig",
    "MeshConfig",
    "RetryPolicy",
    "ServerHyperparams",
    "ServingConfig",
    "UnknownConfigKeyError",
    "asdict",
    "client_hyperparams",
    "dataset_config",
    "make_config",
    "override",
    "server_hyperparams",
    # logging
    "CallbackRegistry",
    "VerboseLogger",
    # messages
    "DataMsg",
    "DownloadMsg",
    "Events",
    "GradientMsg",
    "ModelMsg",
    "UploadMsg",
    # serialization
    "SerializedArray",
    "deserialize_array",
    "deserialize_tree",
    "flat_deserialize",
    "flat_serialize",
    "mean_serialized",
    "pack_bytes",
    "serialize_array",
    "serialize_tree",
    "stack_serialized",
    "tree_from_bytes",
    "tree_to_bytes",
    "unpack_bytes",
]
