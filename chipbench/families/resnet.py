"""Family ``resnet``: bottleneck ResNet v1.5 (``bluefog_tpu.models.ResNet``)
trained with batch statistics, cross entropy on seeded random images."""

import dataclasses

import jax
import jax.numpy as jnp
import optax

from bluefog_tpu.models.resnet import BottleneckBlock, ResNet

from chipbench import flops


@dataclasses.dataclass(frozen=True)
class ResNetFamily:
    model: ResNet
    batch: int
    image_size: int
    item = "images"

    @property
    def items_per_step(self) -> int:
        return self.batch

    def _images(self, key, n):
        return jax.random.normal(
            key, (n, self.image_size, self.image_size, 3), self.model.dtype)

    def init(self, key):
        variables = self.model.init(key, self._images(key, 1), train=True)
        return variables["params"], variables["batch_stats"]

    def make_batch(self, key):
        k_img, k_lab = jax.random.split(key)
        labels = jax.random.randint(
            k_lab, (self.batch,), 0, self.model.num_classes, dtype=jnp.int32)
        return self._images(k_img, self.batch), labels

    def loss(self, params, model_state, batch):
        images, labels = batch
        logits, mutated = self.model.apply(
            {"params": params, "batch_stats": model_state}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    def flops_per_item(self) -> float:
        m = self.model
        return flops.resnet_train_flops_per_image(
            self.image_size, m.stage_sizes, m.num_filters, m.num_classes)

    def kernel_costs(self) -> dict:
        return {}


def build(config: dict, traffic: dict) -> ResNetFamily:
    if config["block"] != "bottleneck" or not config["stride_on_3x3"]:
        raise SystemExit("chipbench: family resnet builds the bottleneck "
                         "v1.5 layout only")
    model = ResNet(
        stage_sizes=tuple(config["stage_sizes"]), block_cls=BottleneckBlock,
        num_classes=config["num_classes"], num_filters=config["num_filters"],
        dtype=jnp.dtype(config["compute_dtype"]), stem="conv")
    return ResNetFamily(model, traffic["batch"], traffic["image_size"])
