"""Model zoo (symbol builders) — reference example/image-classification/symbols/."""
from . import resnet
from . import resnet_v1
from . import resnext
from . import lenet
from . import mlp
from . import alexnet
from . import vgg
from . import mobilenet
from . import googlenet
from . import inception_v4
from . import transformer
from . import afmoe
from . import sarvam_mla      # a serving step's block, not a symbol builder
from . import lfm2_moe        # likewise

get_resnet = resnet.get_symbol
get_lenet = lenet.get_symbol
get_mlp = mlp.get_symbol
get_transformer = transformer.get_symbol
