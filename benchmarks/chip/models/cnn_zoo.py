"""The image-mode zoo of client CNNs: client ``cid`` runs the layer list
``archs[cid % len(archs)]`` (``fdbench.layers``). The program builds the
same zoo from the dataset's image shape, so it takes no widths."""
from fdbench import layers


def layer_list(config: dict, cid: int) -> list:
    archs = config["archs"]
    return archs[cid % len(archs)]


def param_shapes(config: dict, cid: int):
    return layers.param_shapes(layer_list(config, cid), config["input"])


def forward_flops(config: dict, cid: int) -> int:
    return layers.forward_flops(layer_list(config, cid), config["input"])


def filter_dim(config: dict) -> int:
    return layers.filter_dim(config["input"])


def init_params(key, config: dict, cid: int):
    return layers.init_params(key, layer_list(config, cid), config["input"])


def make_apply(config: dict, cid: int, precision):
    return layers.make_apply(layer_list(config, cid), config["num_classes"],
                             precision)


def arch_key(config: dict, cid: int) -> str:
    return repr(layer_list(config, cid))


def build_kwargs(config: dict) -> dict:
    return {}
