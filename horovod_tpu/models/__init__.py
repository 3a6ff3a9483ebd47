from .resnet import (ResNet, ResNet18, ResNet34, ResNet50, ResNet101,
                     ResNet152, ResNetTiny)

__all__ = [
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101", "ResNet152",
    "ResNetTiny",
]


def __getattr__(name):
    import importlib
    lazy = {"bert": ".bert", "llama": ".llama", "mixtral": ".mixtral",
            "dlrm": ".dlrm", "decode": ".decode",
            "latent_moe": ".latent_moe", "looped": ".looped",
            "window_moe": ".window_moe", "conv_moe": ".conv_moe",
            "delta_moe": ".delta_moe"}
    for mod, path in lazy.items():
        if name == mod:
            try:
                return importlib.import_module(path, __name__)
            except ModuleNotFoundError as e:
                if e.name != f"{__name__}.{mod}":
                    raise  # a real missing dependency inside the submodule
                raise AttributeError(name) from e
    raise AttributeError(f"module 'horovod_tpu.models' has no attribute {name!r}")
