from galvatron_tpu.models.olmoe import main

raise SystemExit(main())
